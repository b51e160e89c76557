import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell on the production mesh and extract the roofline inputs.

MUST be run as its own process (the device-count flag above is locked at
first jax init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out results/dryrun

Per cell it jits the *real* step (full train step with optimizer update, or
prefill / one-token decode against the full-size KV cache), lowers with
ShapeDtypeStruct inputs (no allocation), compiles under GSPMD, and records:

  memory_analysis        — per-device argument/output/temp/peak bytes
  cost_analysis          — XLA's flops/bytes counters (loop bodies counted
                           once — see hloparse docstring)
  hloparse.analyze       — loop-aware per-device FLOPs / HBM bytes /
                           collective bytes from the post-SPMD HLO text

Variants (--variant) are the §Perf hillclimb levers:
  baseline      bf16 params/compute, paper-faithful execution
  seqshard      + Megatron-style sequence-parallel activations
  int8w         int8-resident weights (serving cells; the paper's knob)
  int8w+seqshard, gradcomp (int8 EF cross-pod gradients; train, multipod)
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs import ALL_SHAPES, ARCH_IDS, cell_applicable, get_config
from ..configs.base import ModelConfig, ShapeSpec
from ..core.quantization import QuantConfig, quantize_tree_stacked
from ..models.registry import build_model
from ..optim import AdamW, AdamWState
from ..parallel.sharding import (activation_sharding, batch_shardings,
                                 default_rules, replicated, tree_shardings)
from .mesh import make_production_mesh

#: archs large enough that the residual stream must be sequence-sharded
#: between blocks for activations (saved-for-backward) to fit HBM
BIG_ARCHS = ("granite-34b", "internlm2-20b", "kimi-k2-1t-a32b",
             "qwen3-moe-235b-a22b", "jamba-1.5-large-398b",
             "llava-next-mistral-7b")


def _to_bf16(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, dtype="bfloat16",
                               param_dtype="bfloat16")


def _cell_fn_and_args(model, cfg: ModelConfig, shape: ShapeSpec,
                      variant: str, mesh, rules):
    """Build (fn, arg_structs, in_shardings, donate) for one cell."""
    axes = model.logical_axes()
    p_structs = model.param_structs()
    p_sh = tree_shardings(axes, p_structs, rules, mesh)
    in_specs = model.input_specs(shape)
    b_sh = batch_shardings(in_specs, rules, mesh)
    int8w = "int8w" in variant

    if int8w:
        # int8-resident weights: the serving-side realization of the paper's
        # bit-width knob (QuantizedTensor leaves dequantize on read)
        qcfg = QuantConfig(bits=8, granularity="per-channel")
        qp_structs = jax.eval_shape(
            lambda t: quantize_tree_stacked(t, qcfg), p_structs)
        qp_sh = _shard_quantized(p_sh, p_structs, qp_structs, mesh)
        p_structs, p_sh = qp_structs, qp_sh

    if shape.kind == "train":
        opt = AdamW(learning_rate=1e-4)
        o_structs = jax.eval_shape(opt.init, p_structs) if not int8w else None

        if "gradcomp" in variant and "pod" in mesh.axis_names:
            # explicit pod axis: per-pod grads -> int8 EF -> all-gather(int8)
            from jax.sharding import PartitionSpec as P
            from ..optim import compress_tree

            def train_step(params, opt_state, batch):
                def per_pod(params, opt_state, batch):
                    loss, grads = jax.value_and_grad(model.loss)(params,
                                                                 batch)
                    err = jax.tree_util.tree_map(
                        lambda g: jnp.zeros(g.shape, jnp.float32), grads)
                    grads, _ = compress_tree(grads, err, axis_name="pod")
                    params, opt_state, _ = opt.update(grads, opt_state,
                                                      params)
                    return params, opt_state, jax.lax.pmean(loss, "pod")
                return jax.shard_map(
                    per_pod, mesh=mesh,
                    in_specs=(P(), P(), P("pod")),
                    out_specs=(P(), P(), P()),
                    axis_names={"pod"}, check_vma=False)(
                        params, opt_state, batch)
        else:
            def train_step(params, opt_state, batch):
                loss, grads = jax.value_and_grad(model.loss)(params, batch)
                params, opt_state, metrics = opt.update(grads, opt_state,
                                                        params)
                return params, opt_state, loss

        o_sh = AdamWState(step=replicated(mesh),
                          m=jax.tree_util.tree_map(lambda s: s, p_sh),
                          v=jax.tree_util.tree_map(lambda s: s, p_sh))
        fn = train_step
        args = (p_structs, o_structs, in_specs)
        shardings = (p_sh, o_sh, b_sh)
        donate = (0, 1)
    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch)
        fn = prefill_step
        args = (p_structs, in_specs)
        shardings = (p_sh, b_sh)
        donate = ()
    else:  # decode
        c_structs = model.cache_specs(shape)
        c_axes = model.cache_axes()
        c_sh = tree_shardings(c_axes, c_structs, rules, mesh)

        def decode_step(params, cache, batch):
            return model.decode_step(params, cache, batch)
        fn = decode_step
        args = (p_structs, c_structs, in_specs)
        shardings = (p_sh, c_sh, b_sh)
        donate = (1,)
    return fn, args, shardings, donate


def _shard_quantized(p_sh, p_structs, qp_structs, mesh):
    """Shardings for the quantized tree: codes inherit the float leaf's
    sharding, scales replicate (tiny), non-quantized leaves keep theirs.

    The quantized tree is structurally the float tree with some leaves
    replaced by QuantizedTensor nodes, so a structural map against the
    original sharding tree pairs every leaf exactly."""
    del p_structs
    from ..core.quantization import QuantizedTensor

    def one(qt, sh):
        if isinstance(qt, QuantizedTensor):
            return QuantizedTensor(codes=sh, scale=replicated(mesh),
                                   bits=qt.bits, scheme=qt.scheme)
        return sh

    return jax.tree_util.tree_map(
        one, qp_structs, p_sh,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))


def run_cell(arch: str, shape: ShapeSpec, *, multi_pod: bool,
             variant: str = "baseline") -> Dict[str, Any]:
    """Lower+compile one cell; returns the roofline record."""
    from . import hloparse

    cfg = _to_bf16(get_config(arch))
    ok, reason = cell_applicable(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape.name, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
    }
    if not ok:
        rec.update(status="skip", reason=reason)
        return rec

    t0 = time.monotonic()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        long_ctx = shape.name == "long_500k"
        rules = default_rules(cfg, long_context=long_ctx)
        if "cacheshard" in variant:
            # flash-decoding style: KV cache sharded along the sequence
            # axis over the TP group (partial-softmax combine under GSPMD)
            rules["cache_seq"] = "model"
        if "notp" in variant:
            # small models: tensor parallelism wastes the 16-way model axis
            # on per-layer activation all-gathers; replicate weights and
            # give the model axis to the sequence instead (+seqshard)
            for k in ("heads", "kv", "kv_heads", "ffn", "vocab"):
                rules[k] = None
        model = build_model(cfg)
        fn, args, shardings, donate = _cell_fn_and_args(
            model, cfg, shape, variant, mesh, rules)

        seq_spec = None
        if "seqshard" in variant or "notp" in variant or (
                arch in BIG_ARCHS and shape.kind == "train"
                and "noseqshard" not in variant):
            # under gradcomp the pod axis is manual inside the shard_map,
            # so the activation constraint may only name the auto axes
            batch_axes = ("pod", "data") \
                if (multi_pod and "gradcomp" not in variant) else ("data",)
            seq_spec = P(batch_axes if len(batch_axes) > 1
                         else batch_axes[0], "model")

        from ..parallel.sharding import flash_attention_mode
        flash_ctx = flash_attention_mode(
            mesh if "flash" in variant else None)
        with jax.set_mesh(mesh):
            with activation_sharding(seq_spec), flash_ctx:
                jitted = jax.jit(fn, in_shardings=shardings,
                                 donate_argnums=donate)
                lowered = jitted.lower(*args)
            compiled = lowered.compile()

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        costs = hloparse.analyze(hlo_text)

        rec.update(
            status="ok",
            compile_s=round(time.monotonic() - t0, 1),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "generated_code_bytes": getattr(
                    mem, "generated_code_size_in_bytes", 0),
            },
            cost_analysis={
                "flops": float(cost.get("flops", 0.0)) if cost else 0.0,
                "bytes_accessed": float(cost.get("bytes accessed", 0.0))
                if cost else 0.0,
            },
            hlo={
                "flops_per_device": costs.flops,
                "hbm_bytes_per_device": costs.hbm_bytes,
                "collective_bytes_per_device": costs.collective_bytes,
                "collective_breakdown": costs.collective_breakdown,
                "n_while": costs.n_while,
                "trip_counts": costs.trip_counts[:32],
            },
            model_stats={
                "params": cfg.param_count(),
                "active_params": cfg.active_param_count(),
                "tokens": shape.global_batch * (
                    shape.seq_len if shape.kind != "decode" else 1),
                "kind": shape.kind,
            },
        )
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:],
                   compile_s=round(time.monotonic() - t0, 1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' or comma list")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(ALL_SHAPES) if args.shape == "all" else [
        s for s in ALL_SHAPES if s.name in args.shape.split(",")]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, multi_pod=mp,
                               variant=args.variant)
                results.append(rec)
                tag = f"{arch}|{shape.name}|{rec['mesh']}|{args.variant}"
                status = rec["status"]
                extra = ""
                if status == "ok":
                    mb = rec["memory"]["argument_bytes"] / 2 ** 30
                    extra = (f" args={mb:.2f}GiB "
                             f"flops/dev={rec['hlo']['flops_per_device']:.3g}"
                             f" coll/dev="
                             f"{rec['hlo']['collective_bytes_per_device']:.3g}"
                             f" ({rec['compile_s']}s)")
                elif status == "error":
                    extra = " " + rec["error"][:160]
                print(f"[{status:5s}] {tag}{extra}", flush=True)
                fname = (f"{arch}_{shape.name}_{rec['mesh'].replace('x','-')}"
                         f"_{args.variant}.json")
                with open(os.path.join(args.out, fname), "w") as f:
                    json.dump(rec, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndone: {n_ok} ok, {n_skip} skip, {n_err} error "
          f"of {len(results)} cells")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
