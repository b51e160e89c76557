"""Pallas TPU kernel: fused causal/GQA flash attention (forward).

The roofline baseline (EXPERIMENTS.md §Roofline) shows every big cell
memory-bound, dominated by the blockwise-attention score/probability blocks
crossing HBM at fusion boundaries (~2048x-replicated [512,512] f32 tiles per
layer).  The fix is the canonical one: keep the whole online-softmax
recurrence in VMEM.  HBM traffic collapses to q+k+v+out (+lse), which is
what the §Perf "flash" variant accounts.

Layout: q [B, H, S, dh], k/v [B, KV, T, dh], H = KV * G (GQA: the k/v index
map folds the group so KV tiles are fetched once per group — the HBM saving
GQA exists for).  Grid (B*H, nq, nk), kv innermost; m/l/acc scratch persists
across the kv axis and flushes at nk-1 — same accumulation pattern as
qmm.py.  Causal masking skips fully-masked kv tiles via ``pl.when``.

VMEM at defaults (bq=bk=512, dh<=128): q 256K, k/v 512K, acc 256K, scores
2x1MB -> ~3.5 MiB of 16 MiB; dh=256 still fits.

The backward pass stays on the blockwise-XLA path: this paper's hot path is
*inference* (co-inference serving; prefill + decode), and the serving step
never differentiates.  ``flash_attention`` is therefore wrapped in a
``custom_vjp`` whose bwd recomputes with the blockwise reference — training
keeps working, at baseline traffic (documented in DESIGN.md §6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_env import use_interpret

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      n_k: int, bq: int, bk: int, causal: bool,
                      window: int, scale: float):
    i = pl.program_id(1)      # q block
    j = pl.program_id(2)      # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * bq
    k_start = j * bk
    # causal: skip kv tiles strictly above the diagonal band
    run = True
    if causal:
        run = k_start <= q_start + bq - 1

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # [bq, dh]
        k = k_ref[0].astype(jnp.float32)              # [bk, dh]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                            # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q [B, H, S, dh]; k, v [B, KV, T, dh]; H = KV * G.  Returns [B,H,S,dh].

    S and T must be multiples of the block sizes (callers pad; all assigned
    shape cells are 128-aligned).
    """
    b, h, s, dh = q.shape
    _, kv, t, _ = k.shape
    assert h % kv == 0, (h, kv)
    g = h // kv
    bq = min(block_q, s)
    bk = min(block_k, t)
    assert s % bq == 0 and t % bk == 0, (s, t, bq, bk)
    n_q, n_k = s // bq, t // bk
    scale = dh ** -0.5

    kernel = functools.partial(
        _flash_fwd_kernel, n_k=n_k, bq=bq, bk=bk, causal=causal,
        window=window, scale=scale)
    qr = q.reshape(b * h, s, dh)
    kr = k.reshape(b * kv, t, dh)
    vr = v.reshape(b * kv, t, dh)

    out = pl.pallas_call(
        kernel,
        name="flash_attn",
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, i, j: (bh, i, 0)),
            # GQA fold: query head bh -> kv head bh//g (per batch)
            pl.BlockSpec((1, bk, dh),
                         lambda bh, i, j, g=g, h=h, kv=kv:
                         ((bh // h) * kv + (bh % h) // g, j, 0)),
            pl.BlockSpec((1, bk, dh),
                         lambda bh, i, j, g=g, h=h, kv=kv:
                         ((bh // h) * kv + (bh % h) // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, s, dh)


# ---------------------------------------------------------------------------
# differentiable wrapper (bwd = blockwise-XLA recompute; see module docstring)
# ---------------------------------------------------------------------------

def _ref_attention(q, k, v, causal, window):
    """Oracle in the kernel's [B, H, S, dh] layout (GQA expanded)."""
    b, h, s, dh = q.shape
    kv = k.shape[1]
    ke = jnp.repeat(k, h // kv, axis=1)
    ve = jnp.repeat(v, h // kv, axis=1)
    sc = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                    ke.astype(jnp.float32)) * dh ** -0.5
    t = ke.shape[2]
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p,
                      ve.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    interpret: bool | None = None):
    """Fused attention: Pallas on TPU, interpret elsewhere (tests)."""
    interpret = use_interpret() if interpret is None else interpret
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=interpret)


def _fa_fwd(q, k, v, causal, window, interpret):
    out = flash_attention(q, k, v, causal, window, interpret)
    return out, (q, k, v)


def _fa_bwd(causal, window, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref_attention(q_, k_, v_, causal,
                                                       window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
