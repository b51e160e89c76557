"""Distributed training loop with QAT hooks, checkpointing and compressed
cross-pod gradients.

Composition (bottom to top):

  model.loss                      — any repro.models family
  qat.fake_quantize_agent         — agent-partition fake quant (optional)
  value_and_grad + AdamW          — from-scratch optimizer
  grad_compress (int8 + EF)       — cross-pod all-reduce at 1 byte/elem
  pjit w/ logical-axis shardings  — DP/TP/EP/FSDP per parallel/sharding.py
  shard_map(axis_names={'pod'})   — manual pod axis when the mesh has one,
                                    so the pod all-reduce is explicit and
                                    quantized; 'data'/'model' stay Auto
  CheckpointManager               — async save, restore-on-start

The same ``Trainer`` serves the CPU tests (1-device mesh), the examples
(host mesh) and the dry-run (512-device production mesh; lower/compile only).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..checkpoint import CheckpointManager
from ..core.quantization import QuantConfig
from ..optim import AdamW, AdamWState, compress_tree, init_error_state
from ..parallel.sharding import (batch_shardings, default_rules, replicated,
                                 tree_shardings)
from . import qat as qat_mod


@dataclasses.dataclass
class TrainConfig:
    qat_bits: int = 0                 # 0 disables QAT
    qat_scheme: str = "uniform"
    grad_compression: str = "none"    # 'none' | 'int8_ef'
    log_every: int = 10
    remat: bool = True                # models already checkpoint per-layer


class Trainer:
    """Owns jitted step + state; one instance per (model, mesh)."""

    def __init__(self, model, optimizer: AdamW, mesh: Mesh,
                 train_cfg: Optional[TrainConfig] = None,
                 rules: Optional[Dict[str, Any]] = None,
                 ckpt: Optional[CheckpointManager] = None):
        self.model = model
        self.cfg = model.cfg
        self.opt = optimizer
        self.mesh = mesh
        self.tc = train_cfg or TrainConfig()
        self.rules = rules if rules is not None else default_rules(self.cfg)
        self.ckpt = ckpt
        self._axes = model.logical_axes()
        self._step_fn = None
        self.step = 0

        qcfg = None
        if self.tc.qat_bits > 0:
            qcfg = QuantConfig(bits=self.tc.qat_bits,
                               scheme=self.tc.qat_scheme,
                               granularity="per-channel")
        self.qcfg = qcfg

    # ------------------------------------------------------------------
    # shardings
    # ------------------------------------------------------------------
    def param_shardings(self):
        structs = self.model.param_structs()
        return tree_shardings(self._axes, structs, self.rules, self.mesh)

    def opt_shardings(self, param_sh):
        # m/v mirror params; step is replicated
        return AdamWState(step=replicated(self.mesh), m=param_sh,
                          v=jax.tree_util.tree_map(lambda s: s, param_sh))

    def batch_sharding_for(self, batch_struct):
        return batch_shardings(batch_struct, self.rules, self.mesh)

    # ------------------------------------------------------------------
    # step construction
    # ------------------------------------------------------------------
    def _loss_fn(self, params, batch):
        if self.qcfg is not None:
            params = qat_mod.fake_quantize_agent(
                params, self._axes, self.cfg, self.qcfg)
        return self.model.loss(params, batch)

    def _plain_step(self, params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(self._loss_fn)(params, batch)
        if self.tc.grad_compression == "int8_ef":
            grads, err = compress_tree(grads, err, axis_name=None)
        params, opt_state, metrics = self.opt.update(grads, opt_state,
                                                     params)
        metrics["loss"] = loss
        return params, opt_state, err, metrics

    def _podwise_step(self, params, opt_state, err, batch):
        """Manual 'pod' axis: per-pod grads -> int8 EF compress -> psum."""
        def per_pod(params, opt_state, err, batch):
            loss, grads = jax.value_and_grad(self._loss_fn)(params, batch)
            grads, err = compress_tree(grads, err, axis_name="pod")
            loss = jax.lax.pmean(loss, "pod")
            params, opt_state, metrics = self.opt.update(grads, opt_state,
                                                         params)
            metrics["loss"] = loss
            return params, opt_state, err, metrics

        # params/opt/err replicated over 'pod' (P() on the pod axis; their
        # data/model sharding is handled by the Auto axes), batch split on it
        return jax.shard_map(
            per_pod, mesh=self.mesh,
            in_specs=(P(), P(), P(), P("pod")),
            out_specs=(P(), P(), P(), P()),
            axis_names={"pod"})(params, opt_state, err, batch)

    def build_step(self, batch_struct) -> Callable:
        param_sh = self.param_shardings()
        opt_sh = self.opt_shardings(param_sh)
        batch_sh = self.batch_sharding_for(batch_struct)
        err_sh = param_sh if self.tc.grad_compression == "int8_ef" else \
            replicated(self.mesh)
        has_pod = "pod" in self.mesh.axis_names
        body = self._podwise_step if (
            has_pod and self.tc.grad_compression == "int8_ef") \
            else self._plain_step

        metrics_sh = {"loss": replicated(self.mesh),
                      "grad_norm": replicated(self.mesh),
                      "lr": replicated(self.mesh)}
        self._step_fn = jax.jit(
            body,
            in_shardings=(param_sh, opt_sh, err_sh, batch_sh),
            out_shardings=(param_sh, opt_sh, err_sh, metrics_sh),
            donate_argnums=(0, 1, 2),
        )
        return self._step_fn

    # ------------------------------------------------------------------
    # state init / restore
    # ------------------------------------------------------------------
    def init_state(self, rng):
        with jax.set_mesh(self.mesh):
            params = jax.jit(
                self.model.init,
                out_shardings=self.param_shardings())(rng)
        opt_state = self.opt.init(params)
        err = (init_error_state(params)
               if self.tc.grad_compression == "int8_ef"
               else jnp.zeros((), jnp.float32))
        return params, opt_state, err

    def maybe_restore(self, params, opt_state, err):
        """Resume from the newest checkpoint if one exists."""
        if self.ckpt is None:
            return params, opt_state, err, 0
        state = {"params": params, "opt": opt_state, "err": err}
        sh = {"params": self.param_shardings(),
              "opt": self.opt_shardings(self.param_shardings()),
              "err": jax.tree_util.tree_map(lambda _: replicated(self.mesh),
                                            err)}
        out = self.ckpt.restore_latest(state, sh)
        if out is None:
            return params, opt_state, err, 0
        tree, manifest = out
        self.step = int(manifest["metadata"].get("data_step",
                                                 manifest["step"]))
        return tree["params"], tree["opt"], tree["err"], self.step

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------
    def fit(self, loader, num_steps: int, rng=None,
            state=None, on_metrics: Optional[Callable] = None):
        """Run ``num_steps`` steps; returns (state, history)."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if state is None:
            params, opt_state, err = self.init_state(rng)
            params, opt_state, err, start = self.maybe_restore(
                params, opt_state, err)
            loader.seek(start)
        else:
            params, opt_state, err = state
            start = self.step

        if self._step_fn is None:
            self.build_step(loader.peek_structure())

        history = []
        t_last = time.monotonic()
        with jax.set_mesh(self.mesh):
            for step in range(start, start + num_steps):
                batch = next(loader)
                params, opt_state, err, metrics = self._step_fn(
                    params, opt_state, err, batch)
                self.step = step + 1
                if (step + 1) % self.tc.log_every == 0 or step == start:
                    m = {k: float(v) for k, v in metrics.items()}
                    m["step"] = step + 1
                    m["steps_per_s"] = self.tc.log_every / max(
                        time.monotonic() - t_last, 1e-9)
                    t_last = time.monotonic()
                    history.append(m)
                    if on_metrics:
                        on_metrics(m)
                if self.ckpt is not None and self.ckpt.should_save(step + 1):
                    self.ckpt.save_async(
                        step + 1,
                        {"params": params, "opt": opt_state, "err": err},
                        metadata={"data_step": step + 1})
        if self.ckpt is not None:
            self.ckpt.wait()
        return (params, opt_state, err), history
