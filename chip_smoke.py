#!/usr/bin/env python3
"""Chip smoke: serve qwen2-0.5b at its published widths on one TPU chip.

    python chip_smoke.py

One process, one chip, one model (``configs/qwen2_0_5b.py`` ``FULL``:
24 layers, d_model 896, vocab 151936; random weights from PRNGKey(0)).
Four phases, each of which must pass:

  (a) the backend is a TPU and Pallas kernels compile (no interpret mode);
  (b) each serving-path kernel — int8 and packed-int4 quantized matmul,
      fused group quantizer, quantized decode attention — compiled at
      qwen2-0.5b widths matches its ``kernels/ref.py`` oracle within the
      tolerance stated below, with ``tpu_custom_call`` in its HLO;
  (c) continuous-batching decode through ``repro.launch.serve.main
      --decode`` serves a few requests and returns 0;
  (d) the prefill kernel path, ``CoInferenceEngine(path="kernel",
      compiled=True)`` at b̂=8 and then b̂=4, serves a batch with
      ``agent_path`` ``kernel-int8`` / ``kernel-int4`` and finite logits.

Wall seconds are chip wall times on the host clock, taken after results
are on the host.  Any failure raises and exits non-zero; off a TPU the
script exits 1 before doing anything.  The last line of standard output
is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.qwen2_0_5b import FULL as CFG  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.decode_attn import (  # noqa: E402
    cache_layout, quantized_decode_attention)
from repro.kernels.pallas_env import use_interpret  # noqa: E402
from repro.kernels.quantize import kv_quantize  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# phase (b) tolerance: max |kernel - oracle| / max |oracle| over the
# output.  The oracle runs at "highest" matmul precision; the bound
# leaves room for a kernel that feeds the MXU bf16 passes, and is an
# order of magnitude below what a misplaced scale group or cache tile
# produces.
KERNEL_RTOL = 1e-2
# group quantizer: scales agree to float rounding; a code may differ by
# one level where w / scale lands on a rounding tie
SCALE_RTOL = 1e-6
CODE_MISMATCH_FRACTION = 1e-4

DECODE_ARGS = ["--arch", CFG.name, "--decode", "--requests", "4",
               "--max-new", "8", "--seq", "32"]
KERNEL_PATH_BATCH = (4, 32)         # (requests, tokens) per served batch


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def run_compiled(fn, *args):
    """Compile ``fn`` for ``args``, run it, and return (host result,
    compile s, run s, whether the HLO holds a Mosaic kernel)."""
    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.tree_util.tree_map(np.asarray, exe(*args))
    t2 = time.perf_counter()
    return out, t1 - t0, t2 - t1, "tpu_custom_call" in exe.as_text()


def rel_err(out, want) -> float:
    out = np.asarray(out, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(out - want)) / np.max(np.abs(want)))


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.tree_util.tree_map(np.asarray, jax.jit(fn)(*args))


def phase_kernels() -> None:
    """(b) the four serving-path kernels at qwen2-0.5b widths."""
    d, f = CFG.d_model, CFG.d_ff
    key = iter(jax.random.split(jax.random.PRNGKey(0), 10))
    x = jax.random.normal(next(key), (128, d), jnp.float32)
    x_ff = jax.random.normal(next(key), (128, f), jnp.float32)

    def report(name, err, tol, t_c, t_r, custom):
        print(f"  kernel {name}: rel err {err:.3e} (tol {tol:g}), "
              f"tpu_custom_call={custom}, chip wall compile {t_c:.3f}s "
              f"run {t_r:.4f}s")
        check(custom, f"{name}: no tpu_custom_call in the compiled HLO")
        check(err <= tol, f"{name}: rel err {err:.3e} > {tol:g}")

    # fused group quantizer, gate/up projection [896, 4864]
    w = jax.random.normal(next(key), (d, f), jnp.float32)
    (codes, scales), t_c, t_r, custom = run_compiled(
        lambda w: ops.group_quantize(w, group_size=128), w)
    codes_r, scales_r = highest(
        lambda w: ref.group_quantize_ref(w, 128), w)
    diff = np.abs(codes.astype(np.int32) - codes_r.astype(np.int32))
    frac = float(np.mean(diff > 0))
    print(f"  kernel group_quantize [{d}, {f}]: codes off by one at "
          f"{frac:.2e} of entries (max diff {int(diff.max())}), "
          f"tpu_custom_call={custom}, chip wall compile {t_c:.3f}s "
          f"run {t_r:.4f}s")
    check(custom, "group_quantize: no tpu_custom_call in the compiled HLO")
    check(diff.max() <= 1 and frac <= CODE_MISMATCH_FRACTION,
          f"group_quantize: codes differ (max {diff.max()}, frac {frac})")
    np.testing.assert_allclose(scales, scales_r, rtol=SCALE_RTOL)

    # int8 matmul on the int8 codes above: x [128, 896] @ [896, 4864]
    out, t_c, t_r, custom = run_compiled(ops.quantized_matmul, x,
                                         jnp.asarray(codes),
                                         jnp.asarray(scales))
    want = highest(ref.qmm_ref, x, codes, scales)
    report(f"qmm int8 [128, {d}] @ [{d}, {f}]", rel_err(out, want),
           KERNEL_RTOL, t_c, t_r, custom)

    # packed int4: K=896 (q projection, one full-K block) and K=4864
    # (down projection, tiled along K)
    for k, n, xin in ((d, CFG.q_dim, x), (f, d, x_ff)):
        w4 = jax.random.normal(next(key), (k, n), jnp.float32)
        c4, s4 = ref.group_quantize_ref(w4, 128, bits=4)
        packed = ref.pack_int4_ref(c4)
        out, t_c, t_r, custom = run_compiled(ops.quantized_matmul_int4,
                                             xin, packed, s4)
        want = highest(ref.qmm_int4_ref, xin, packed, s4)
        report(f"qmm int4 [128, {k}] @ [{k}, {n}]", rel_err(out, want),
               KERNEL_RTOL, t_c, t_r, custom)

    # quantized decode attention over the largest warmed cache bucket:
    # one decode step's write and attend, on layer 1 of a 3-deep stack
    b, t, h, kv, dh = 4, 256, CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    q = jax.random.normal(next(key), (b, 1, h, dh), jnp.float32)
    kc, ks = cache_layout(*kv_quantize(
        jax.random.normal(next(key), (3, b, t, kv, dh), jnp.float32), 8))
    vc, vs = cache_layout(*kv_quantize(
        jax.random.normal(next(key), (3, b, t, kv, dh), jnp.float32), 8))
    (kn, ksn), (vn, vsn) = (kv_quantize(
        jax.random.normal(next(key), (b, kv, dh), jnp.float32), 8)
        for _ in range(2))
    lens = jnp.asarray([1, 77, 200, 256], jnp.int32)
    layer = jnp.asarray(1, jnp.int32)
    (out, stack), t_c, t_r, custom = run_compiled(
        quantized_decode_attention, q, kc, vc, ks, vs, lens, layer,
        (kn, vn, ksn, vsn))
    want = highest(ref.decode_attention_ref, q, *stack, lens, 1)
    report(f"decode_attention B={b} T={t} H={h} KV={kv} dh={dh}",
           rel_err(out, want), KERNEL_RTOL, t_c, t_r, custom)


def phase_decode() -> None:
    """(c) continuous-batching decode through the serving CLI."""
    from repro.launch import serve
    t0 = time.perf_counter()
    rc = serve.main(DECODE_ARGS)
    dt = time.perf_counter() - t0
    print(f"  serve --decode rc={rc}: chip wall {dt:.2f}s "
          "(compile included)")
    check(rc == 0, f"serve {' '.join(DECODE_ARGS)} returned {rc}")


def phase_kernel_path() -> None:
    """(d) the prefill kernel path at b̂=8, then b̂=4."""
    from repro.core.cost_model import SystemParams
    from repro.models.registry import build_model
    from repro.runtime import CoInferenceEngine

    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    b, s = KERNEL_PATH_BATCH
    per_layer = CFG.active_param_count() / CFG.n_layers
    sysp = SystemParams(
        n_flop_agent=2.0 * per_layer * CFG.split_layer * b * s,
        n_flop_server=2.0 * per_layer * (CFG.n_layers - CFG.split_layer)
        * b * s)
    eng = CoInferenceEngine(model, params, sysp, path="kernel",
                            compiled=True)
    tokens = np.random.default_rng(0).integers(0, CFG.vocab_size, (b, s))
    for bits in (8, 4):
        t0 = time.perf_counter()
        eng.configure(bits)
        eng.precompile(b, s)
        t1 = time.perf_counter()
        logits, _ = eng.serve_batch({"tokens": tokens})
        logits = np.asarray(logits)
        t2 = time.perf_counter()
        print(f"  kernel path b_hat={bits}: agent_path={eng.agent_path} "
              f"logits {logits.shape}, chip wall quantize+compile "
              f"{t1 - t0:.2f}s serve {t2 - t1:.3f}s")
        check(eng.agent_path == f"kernel-int{bits}",
              f"b_hat={bits} served through {eng.agent_path}")
        check(logits.shape == (b, s, CFG.vocab_size),
              f"logits shape {logits.shape}")
        check(np.isfinite(logits).all(), f"b_hat={bits}: non-finite logits")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"error: chip_smoke needs a TPU; jax found {dev.platform}",
              file=sys.stderr)
        return 1
    check(not use_interpret(), "Pallas interpret mode is on a TPU backend")
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {enable_compile_cache()}")
    print(f"model: {CFG.name} layers={CFG.n_layers} d_model={CFG.d_model} "
          f"vocab={CFG.vocab_size}")
    for name, phase in (("(b) kernels", phase_kernels),
                        ("(c) decode", phase_decode),
                        ("(d) kernel path", phase_kernel_path)):
        print(f"phase {name}", flush=True)
        t0 = time.perf_counter()
        phase()
        print(f"phase {name} passed: chip wall {time.perf_counter() - t0:.2f}s "
              f"peak_bytes_in_use={peak_bytes()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
