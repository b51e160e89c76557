"""Decoder-only language model (dense / MoE / VLM / audio-decoder families).

One scan-over-layers transformer whose per-layer block is configured by the
``ModelConfig``.  Provides the full protocol the framework needs:

  init / logical_axes / param_structs      (params + sharding metadata)
  loss / forward                           (training)
  prefill / init_cache / decode_step       (serving)
  run_layers                               (co-inference split execution)
  input_specs                              (dry-run ShapeDtypeStruct stand-ins)

Multimodal stubs: for ``frontend != none`` the input dict carries precomputed
``embeds`` [B, S_vis, D] (the assignment mandates the modality frontend be a
stub) which are concatenated before the token embeddings.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeSpec
from ..kernels.decode_attn import quantized_decode_attention
from ..kernels.quantize import kv_quantize
from ..parallel.sharding import constrain_activations
from . import layers as L
from . import moe as M


def _split_tree(tree, lo, hi):
    return jax.tree_util.tree_map(lambda a: a[lo:hi], tree)


class DecoderLM:
    """Config-driven decoder-only LM."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._axes = None
        # scan requires layer homogeneity: all layers MoE or all dense
        if cfg.n_experts and cfg.moe_every != 1:
            raise ValueError("DecoderLM supports moe_every=1; interleaved "
                             "MoE belongs to the hybrid model")

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def _build(self, rng):
        cfg = self.cfg
        ks = jax.random.split(rng, 6)
        emb_p, emb_ax = L.init_embeddings(cfg, ks[0])
        attn_p, attn_ax = L.init_attention(cfg, ks[1], layers=cfg.n_layers)
        ln1_p, ln1_ax = L.init_norm(cfg, cfg.d_model)
        ln2_p, ln2_ax = L.init_norm(cfg, cfg.d_model)
        lnf_p, lnf_ax = L.init_norm(cfg, cfg.d_model)

        def stack_norm(p, ax):
            sp = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape),
                p)
            sax = jax.tree_util.tree_map(
                lambda t: ("layers",) + t, ax,
                is_leaf=lambda x: isinstance(x, tuple))
            return sp, sax

        ln1_p, ln1_ax = stack_norm(ln1_p, ln1_ax)
        ln2_p, ln2_ax = stack_norm(ln2_p, ln2_ax)

        if cfg.n_experts:
            ffn_p, ffn_ax = M.init_moe(cfg, ks[2], layers=cfg.n_layers)
        else:
            ffn_p, ffn_ax = L.init_mlp(cfg, ks[2], layers=cfg.n_layers)

        params = {"embed": emb_p,
                  "layers": {"attn": attn_p, "ffn": ffn_p,
                             "ln1": ln1_p, "ln2": ln2_p},
                  "final_norm": lnf_p}
        axes = {"embed": emb_ax,
                "layers": {"attn": attn_ax, "ffn": ffn_ax,
                           "ln1": ln1_ax, "ln2": ln2_ax},
                "final_norm": lnf_ax}
        self._axes = axes
        return params

    def init(self, rng):
        return self._build(rng)

    def logical_axes(self):
        if self._axes is None:
            jax.eval_shape(self._build, jax.random.PRNGKey(0))
        return self._axes

    def param_structs(self):
        return jax.eval_shape(self._build, jax.random.PRNGKey(0))

    # ------------------------------------------------------------------
    # blocks
    # ------------------------------------------------------------------
    def _block(self, lp, x, positions, *, blockwise=True):
        cfg = self.cfg
        h = L.apply_norm(cfg, x, lp["ln1"])
        q, k, v = L.qkv_project(cfg, lp["attn"], h, positions)
        if blockwise:
            attn = L.blockwise_attention(
                q, k, v, causal=True, window=cfg.sliding_window)
        else:  # tiny sequences: direct path (used by smoke tests)
            attn = L.blockwise_attention(
                q, k, v, causal=True, window=cfg.sliding_window,
                q_block=max(16, q.shape[1]), kv_block=max(16, k.shape[1]))
        x = x + attn.reshape(x.shape[:2] + (cfg.q_dim,)) \
            @ lp["attn"]["wo"].astype(x.dtype)
        h2 = L.apply_norm(cfg, x, lp["ln2"])
        if cfg.n_experts:
            y, aux = M.apply_moe(cfg, lp["ffn"], h2)
        else:
            y, aux = L.apply_mlp(cfg, lp["ffn"], h2), jnp.float32(0.0)
        return x + y, aux

    def _run_stack(self, layer_params, x, positions,
                   remat_block: Optional[int] = None):
        cfg = self.cfg
        n = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
        remat_block = cfg.remat_block if remat_block is None else remat_block

        def one(carry, lp):
            x, aux = carry
            x = constrain_activations(x)
            x, a = self._block(lp, x, positions)
            return (x, aux + a), None

        if remat_block > 1 and n % remat_block == 0 and n > remat_block:
            # two-level remat: checkpoint wraps the INNER scan so backward
            # stores only n/remat_block outer carries and recomputes each
            # block — peak activation memory O(n/k + k) instead of O(n).
            nb = n // remat_block
            blk = jax.tree_util.tree_map(
                lambda a: a.reshape((nb, remat_block) + a.shape[1:]),
                layer_params)

            def outer(carry, bp):
                c, _ = jax.lax.scan(one, carry, bp)
                return c, None

            outer = jax.checkpoint(outer)
            (x, aux), _ = jax.lax.scan(outer, (x, jnp.float32(0.0)), blk)
        elif cfg.scan_layers:
            one = jax.checkpoint(one)
            (x, aux), _ = jax.lax.scan(one, (x, jnp.float32(0.0)),
                                       layer_params)
        else:
            aux = jnp.float32(0.0)
            for i in range(n):
                lp = jax.tree_util.tree_map(lambda a: a[i], layer_params)
                x, a = self._block(lp, x, positions)
                aux = aux + a
        return x, aux

    def run_layers(self, params, x, positions, lo: int, hi: int):
        """Co-inference split execution: layers [lo, hi) on activations x."""
        sub = _split_tree(params["layers"], lo, hi)
        return self._run_stack(sub, x, positions, remat_block=0)

    def run_layers_window(self, params, x, positions, lo, hi):
        """Split execution with *runtime* bounds: layers [lo, hi) applied
        through a ``lax.while_loop`` whose trip count XLA cannot see.

        Pass ``lo``/``hi`` as int32 *arrays* (concrete in eager mode,
        traced arguments inside a jit): the loop body then compiles to
        one isolated XLA sub-computation regardless of window size, so
        its bits are identical whether the window runs eagerly or inlined
        in a larger jitted graph — a static-length scan would be unrolled
        and re-fused at short trip counts.  This bit-stability is what
        the compiled serving fast path's bitwise-identity invariant
        builds on (DESIGN.md §10).  Forward-only (no aux, no remat); the
        training path keeps :meth:`_run_stack`'s scan.
        """
        lp = params["layers"]
        lo = jnp.asarray(lo, jnp.int32)
        hi = jnp.asarray(hi, jnp.int32)

        def cond(carry):
            return carry[0] < hi

        def body(carry):
            i, x = carry
            sl = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False), lp)
            x = constrain_activations(x)
            x, _ = self._block(sl, x, positions)
            return (i + 1, x)

        _, x = jax.lax.while_loop(cond, body, (lo, x))
        return x, jnp.float32(0.0)

    # ------------------------------------------------------------------
    # embedding plumbing (handles the multimodal stub)
    # ------------------------------------------------------------------
    def _embed(self, params, batch):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        parts = []
        if "embeds" in batch:
            parts.append(batch["embeds"].astype(dtype))
        if "tokens" in batch:
            parts.append(L.embed_tokens(params["embed"], batch["tokens"],
                                        dtype))
        x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        return x, positions

    def embed(self, params, batch):
        """Public embedding hook: batch dict -> (x [B, S, D], positions
        [B, S]).  The compiled serving fast path (runtime/fastpath.py)
        traces through this; models exposing it (plus ``run_layers``)
        are fast-path capable."""
        return self._embed(params, batch)

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def forward(self, params, batch):
        x, positions = self._embed(params, batch)
        x, aux = self._run_stack(params["layers"], x, positions)
        x = L.apply_norm(self.cfg, x, params["final_norm"])
        return L.unembed(self.cfg, params["embed"], x), aux

    def loss(self, params, batch):
        # CE from hidden states with chunked unembedding — the full
        # [B, S, V] logits tensor never materializes (layers.py docstring)
        x, positions = self._embed(params, batch)
        x, aux = self._run_stack(params["layers"], x, positions)
        x = L.apply_norm(self.cfg, x, params["final_norm"])
        labels = batch["labels"]
        # multimodal: loss only over the trailing text positions
        if x.shape[1] != labels.shape[1]:
            x = x[:, -labels.shape[1]:]
        ce = L.chunked_cross_entropy(self.cfg, x, params["embed"], labels,
                                     batch.get("loss_mask"))
        return ce + 0.01 * aux

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def prefill(self, params, batch, last_index=None):
        """Full-sequence pass building the KV cache; returns (last-position
        logits, cache).

        ``last_index`` ([B] int32, optional) names each row's true final
        prompt position for right-padded batches: logits are gathered
        there instead of at column s-1, and the cache ``len`` becomes
        ``last_index + 1`` per row.  Causal attention makes positions
        <= last_index independent of the padding, so the gathered logits
        and the live cache prefix are bitwise those of the unpadded
        prompt (the decode engine's bucket invariant, DESIGN.md §12).
        """
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        b, s = x.shape[0], x.shape[1]

        # collect per-layer K/V as scan outputs
        def step(x, lp):
            h = L.apply_norm(cfg, x, lp["ln1"])
            q, k, v = L.qkv_project(cfg, lp["attn"], h, positions)
            attn = L.blockwise_attention(q, k, v, causal=True,
                                         window=cfg.sliding_window)
            x = x + attn.reshape(x.shape[:2] + (cfg.q_dim,)) \
                @ lp["attn"]["wo"].astype(x.dtype)
            h2 = L.apply_norm(cfg, x, lp["ln2"])
            if cfg.n_experts:
                y, _ = M.apply_moe(cfg, lp["ffn"], h2)
            else:
                y = L.apply_mlp(cfg, lp["ffn"], h2)
            return x + y, (k.astype(jnp.dtype(cfg.dtype)),
                           v.astype(jnp.dtype(cfg.dtype)))

        x, (ks, vs) = jax.lax.scan(step, x, params["layers"])
        x = L.apply_norm(cfg, x, params["final_norm"])
        if last_index is None:
            sel = x[:, -1:]
            lens = jnp.full((b,), s, jnp.int32)
        else:
            idx = jnp.asarray(last_index, jnp.int32)
            sel = jax.vmap(
                lambda row, i: jax.lax.dynamic_slice_in_dim(row, i, 1, 0)
            )(x, idx)
            lens = idx + 1
        logits = L.unembed(cfg, params["embed"], sel)[:, 0]
        cache = {"k": ks, "v": vs, "len": lens}
        return logits, cache

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
                "len": jnp.zeros((batch,), jnp.int32)}

    def cache_axes(self):
        t = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
        return {"k": t, "v": t, "len": ("batch",)}

    def decode_step(self, params, cache, batch):
        """One token: batch = {'token': [B,1], 'pos': [B]}."""
        cfg = self.cfg
        tok, pos = batch["token"], batch["pos"]
        x = L.embed_tokens(params["embed"], tok, jnp.dtype(cfg.dtype))
        positions = pos[:, None]

        def step(x, lp_and_cache):
            lp, kc, vc = lp_and_cache
            h = L.apply_norm(cfg, x, lp["ln1"])
            q, k, v = L.qkv_project(cfg, lp["attn"], h, positions)
            # write new kv at position pos
            b = x.shape[0]
            kc = jax.vmap(lambda c, kk, pp: jax.lax.dynamic_update_slice(
                c, kk, (pp, 0, 0)))(kc, k, pos)
            vc = jax.vmap(lambda c, vv, pp: jax.lax.dynamic_update_slice(
                c, vv, (pp, 0, 0)))(vc, v, pos)
            attn = L.decode_attention(q, kc, vc, pos + 1,
                                      window=cfg.sliding_window)
            x = x + attn.reshape(b, 1, cfg.q_dim) \
                @ lp["attn"]["wo"].astype(x.dtype)
            h2 = L.apply_norm(cfg, x, lp["ln2"])
            if cfg.n_experts:
                y, _ = M.apply_moe(cfg, lp["ffn"], h2,
                                   path="dense" if cfg.n_experts <= 8
                                   else "dispatch",
                                   group_size=min(1024, b))
            else:
                y = L.apply_mlp(cfg, lp["ffn"], h2)
            return x + y, (kc, vc)

        x, (ks, vs) = jax.lax.scan(step, x,
                                   (params["layers"], cache["k"],
                                    cache["v"]))
        x = L.apply_norm(cfg, x, params["final_norm"])
        logits = L.unembed(cfg, params["embed"], x)[:, 0]
        new_cache = {"k": ks, "v": vs, "len": cache["len"] + 1}
        return logits, new_cache

    def decode_step_q(self, params, qcache, batch, *, b_kv: int):
        """One token straight over the *quantized* cache (DESIGN.md §13).

        ``qcache`` is the decode engine's device-resident container,
        head-major with positions minor: ``k_codes``/``v_codes``
        [L, B, KV, dh, T] (int8 codes for b_kv < 16, the raw cfg.dtype
        container otherwise), ``k_scales``/``v_scales`` [L, B, KV, 1, T]
        f32 (ones for raw), plus per-row ``len``.  Unlike
        :meth:`decode_step`, the cache is never dequantized wholesale,
        and never sliced, transposed or restacked: the layer loop scans
        only the layer parameters and carries the whole stack, and per
        layer :func:`quantized_decode_attention` writes each row's fresh
        entry in place at position ``pos`` and attends over the layer
        where it lies.  The fresh entry is quantized *before* it is
        written, so this step's own attention reads it through the same
        dequant map every later step will.  b_kv >= 16 stores raw values
        with unit scales — an exact path through the identical kernel.
        """
        cfg = self.cfg
        tok, pos = batch["token"], batch["pos"]
        x = L.embed_tokens(params["embed"], tok, jnp.dtype(cfg.dtype))
        positions = pos[:, None]
        b = x.shape[0]

        def step(carry, lp_and_layer):
            x, kc, vc, ksc, vsc = carry
            lp, layer = lp_and_layer
            h = L.apply_norm(cfg, x, lp["ln1"])
            q, k, v = L.qkv_project(cfg, lp["attn"], h, positions)
            k, v = k[:, 0], v[:, 0]                     # [B, KV, dh]
            if b_kv < 16:
                k_new, ks_new = kv_quantize(k, b_kv)
                v_new, vs_new = kv_quantize(v, b_kv)
            else:
                k_new, v_new = k, v
                ks_new = jnp.ones(k.shape[:-1], jnp.float32)
                vs_new = jnp.ones(v.shape[:-1], jnp.float32)
            # the kernel writes the entry where it lies, then attends
            attn, (kc, vc, ksc, vsc) = quantized_decode_attention(
                q, kc, vc, ksc, vsc, pos + 1, layer,
                (k_new, v_new, ks_new, vs_new), window=cfg.sliding_window)
            x = x + attn.reshape(b, 1, cfg.q_dim) \
                @ lp["attn"]["wo"].astype(x.dtype)
            h2 = L.apply_norm(cfg, x, lp["ln2"])
            if cfg.n_experts:
                y, _ = M.apply_moe(cfg, lp["ffn"], h2,
                                   path="dense" if cfg.n_experts <= 8
                                   else "dispatch",
                                   group_size=min(1024, b))
            else:
                y = L.apply_mlp(cfg, lp["ffn"], h2)
            return (x + y, kc, vc, ksc, vsc), None

        carry = (x, qcache["k_codes"], qcache["v_codes"],
                 qcache["k_scales"], qcache["v_scales"])
        (x, ks, vs, kss, vss), _ = jax.lax.scan(
            step, carry, (params["layers"], jnp.arange(cfg.n_layers)))
        x = L.apply_norm(cfg, x, params["final_norm"])
        logits = L.unembed(cfg, params["embed"], x)[:, 0]
        new_cache = {"k_codes": ks, "v_codes": vs, "k_scales": kss,
                     "v_scales": vss, "len": qcache["len"] + 1}
        return logits, new_cache

    # ------------------------------------------------------------------
    # dry-run input specs
    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = jnp.dtype(cfg.dtype)
        sds = jax.ShapeDtypeStruct
        multimodal = cfg.frontend != "none"
        if shape.kind in ("train", "prefill"):
            out = {}
            if multimodal:
                sv = int(S * cfg.vis_frac) // 16 * 16
                st = S - sv
                out["embeds"] = sds((B, sv, cfg.d_model), dt)
                out["tokens"] = sds((B, st), i32)
                if shape.kind == "train":
                    out["labels"] = sds((B, st), i32)
            else:
                out["tokens"] = sds((B, S), i32)
                if shape.kind == "train":
                    out["labels"] = sds((B, S), i32)
            return out
        # decode: one token against a cache of length S
        return {"token": sds((B, 1), i32), "pos": sds((B,), i32)}

    def cache_specs(self, shape: ShapeSpec):
        return jax.eval_shape(
            lambda: self.init_cache(shape.global_batch, shape.seq_len))
