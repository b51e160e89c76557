"""Pallas TPU kernel: flash-decoding attention over quantized KV codes
(DESIGN.md §13).

The decode hot path reads the whole KV cache every token.  Before PR 7
the traced decode step materialized a dequantized copy of the full
``[L, B, T, KV, dh]`` cache in HBM (``kv_dequantize`` then the einsum of
``layers.decode_attention``) — doubling the cache traffic the b_kv
codesign exists to shrink.  This kernel reads the int8-held codes
directly and dequantizes per-tile in VMEM — each position's scale
multiplies its column of scores and of probabilities, so the scales stay
on the lane axis — and HBM sees only the quantized bytes.

Layout: one query vector per sequence (decode), GQA-folded.  Grid is
``(B * KV, T / bt)`` — one program per (row, kv-head) owning the
``[G, dh]`` query group, kv tiles innermost; per-position scales ride as
``[B * KV, 1, T]`` and each program's cache length is a scalar-prefetch
operand in SMEM.  The online-softmax
``m/l/acc`` scratch persists across the tile axis and flushes at the
last tile (``flash.py``'s accumulation pattern).  Cache positions at or
beyond ``cache_len`` are masked; a *fully* masked tile is an exact
no-op on (m, l, acc) — ``max`` over all-NEG_INF scores leaves m, the
correction factor is exp(0) = 1, and the probability tile is exact
zeros — which is what makes cache-bucket padding attention-invisible
bit-for-bit (property-tested in ``tests/test_properties.py``).

The raw b_kv >= 16 container uses the same kernel with all-ones scales:
``x * 1.0`` is exact, so one kernel body serves every rung.

``_tile_update`` holds the per-tile arithmetic and is shared *verbatim*
by the kernel body and the pure-jnp reference
(:func:`quantized_decode_attention_ref`), so kernel-vs-reference parity
is bitwise by construction (``tests/test_decode_kernel.py``).  Off-TPU
the kernel runs under interpret mode (``pallas_env.use_interpret``),
which is how ``DecodeEngine`` and ``greedy_decode_reference`` share it
inside their AOT-compiled step functions on CPU CI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_env import use_interpret

NEG_INF = -1e30


def _tile_update(q, k_codes, v_codes, k_scales, v_scales, t_start,
                 cache_len, m, l, acc, *, window: int, scale: float):
    """One kv tile of the online-softmax recurrence, dequant included.

    q [G, dh] f32; k/v codes [bt, dh] (int8 or float); scales [1, bt] f32
    (one per cache position, along lanes); ``cache_len`` a scalar; m/l
    [G, 1], acc [G, dh] f32 running state.  Returns the updated
    (m, l, acc).  Each position's scale multiplies its score column
    (``q . (c s) = (q . c) s``) and its probability column
    (``sum_t p_t s_t v_t``), so the scales never leave the lane axis.
    Shared by the Pallas kernel body (on VMEM refs) and the jnp reference
    (on array slices): identical ops, identical bits.
    """
    bt = k_codes.shape[0]
    g = q.shape[0]
    k = k_codes.astype(jnp.float32)
    v = v_codes.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * k_scales * scale                                # in-VMEM dequant
    kpos = t_start + jax.lax.broadcasted_iota(jnp.int32, (g, bt), 1)
    valid = kpos < cache_len
    if window > 0:
        valid &= kpos >= cache_len - window
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * corr + jax.lax.dot_general(
        p * v_scales, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


# Interpret-mode pallas evaluates the kernel body as a jitted
# sub-computation per grid step; the reference must run each tile through
# jit the same way, or XLA's within-tile fusion (fma contraction in the
# l/acc updates) drifts the accumulators by a few ULPs once a second tile
# feeds a nonzero carry.  Single jit cache entry per (window, scale).
_tile_update_jit = jax.jit(_tile_update, static_argnames=("window", "scale"))


def _qdecode_kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, n_t: int, bt: int,
                    window: int, scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m, l, acc = _tile_update(
        q_ref[0].astype(jnp.float32), k_ref[0], v_ref[0], ks_ref[0],
        vs_ref[0], j * bt, len_ref[pl.program_id(0)], m_ref[...],
        l_ref[...], acc_ref[...], window=window, scale=scale)
    m_ref[...] = m
    l_ref[...] = l
    acc_ref[...] = acc

    @pl.when(j == n_t - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _fold_heads(q, k_codes, v_codes, k_scales, v_scales, cache_len):
    """[B, ...] layouts -> the kernel's GQA-folded [B*KV, ...] layouts.

    Scales fold to [B*KV, 1, T] so a tile's block is (1, bt) in its last
    two dims, the leading 1 equal to the array's; lengths fold to one
    int32 per program, read from SMEM."""
    b, _, h, dh = q.shape
    t, kv = k_codes.shape[1], k_codes.shape[2]
    g = h // kv
    qr = q.reshape(b, kv, g, dh).reshape(b * kv, g, dh)
    kr = k_codes.transpose(0, 2, 1, 3).reshape(b * kv, t, dh)
    vr = v_codes.transpose(0, 2, 1, 3).reshape(b * kv, t, dh)
    ksr = k_scales.transpose(0, 2, 1).reshape(b * kv, 1, t)
    vsr = v_scales.transpose(0, 2, 1).reshape(b * kv, 1, t)
    lens = jnp.broadcast_to(jnp.reshape(cache_len, (-1, 1)), (b, kv))
    lens = lens.astype(jnp.int32).reshape(b * kv)
    return qr, kr, vr, ksr, vsr, lens


def quantized_decode_attention(q, k_codes, v_codes, k_scales, v_scales,
                               cache_len, *, window: int = 0,
                               block_t: int = 128,
                               interpret: "bool | None" = None):
    """Single-step attention straight over a quantized cache.

    q [B, 1, H, dh]; codes [B, T, KV, dh] (int8 codes, or the raw float
    container for b_kv >= 16); scales [B, T, KV] f32 (ones for raw);
    cache_len [] or [B].  Returns [B, 1, H, dh] in q.dtype — the
    ``layers.decode_attention`` contract, minus the dequantized-cache
    intermediate.  T must be a multiple of the tile size
    ``min(block_t, T)`` (cache buckets are 16·2^k, so it always is).
    """
    interpret = use_interpret() if interpret is None else interpret
    b, _, h, dh = q.shape
    t, kv = k_codes.shape[1], k_codes.shape[2]
    g = h // kv
    bt = min(block_t, t)
    assert t % bt == 0, (t, bt)
    n_t = t // bt
    qr, kr, vr, ksr, vsr, lens = _fold_heads(
        q, k_codes, v_codes, k_scales, v_scales, cache_len)

    kernel = functools.partial(_qdecode_kernel, n_t=n_t, bt=bt,
                               window=window, scale=dh ** -0.5)
    # index maps take the prefetched lengths as a trailing argument
    row = lambda bh, j, lens: (bh, 0, 0)
    tile = lambda bh, j, lens: (bh, j, 0)
    scales = lambda bh, j, lens: (bh, 0, j)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * kv, n_t),
            in_specs=[
                pl.BlockSpec((1, g, dh), row),
                pl.BlockSpec((1, bt, dh), tile),
                pl.BlockSpec((1, bt, dh), tile),
                pl.BlockSpec((1, 1, bt), scales),
                pl.BlockSpec((1, 1, bt), scales),
            ],
            out_specs=pl.BlockSpec((1, g, dh), row),
            scratch_shapes=[
                pltpu.VMEM((g, dh), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b * kv, g, dh), q.dtype),
        interpret=interpret,
    )(lens, qr, kr, vr, ksr, vsr)
    return out.reshape(b, 1, h, dh)


def quantized_decode_attention_ref(q, k_codes, v_codes, k_scales, v_scales,
                                   cache_len, *, window: int = 0,
                                   block_t: int = 128):
    """Pure-jnp oracle running the kernel's exact tile schedule.

    Python loops over (row·kv-head) programs and kv tiles, each tile
    evaluated through the *same* :func:`_tile_update` the kernel body
    calls, jitted per tile exactly as interpret mode executes the kernel
    body — so reference and kernel run the identical compiled tile
    computation and match bitwise (``tests/test_decode_kernel.py``
    asserts it per b_kv rung).
    """
    b, _, h, dh = q.shape
    t = k_codes.shape[1]
    bt = min(block_t, t)
    assert t % bt == 0, (t, bt)
    qr, kr, vr, ksr, vsr, lens = _fold_heads(
        q, k_codes, v_codes, k_scales, v_scales, cache_len)
    scale = dh ** -0.5
    g = qr.shape[1]
    rows = []
    for bh in range(qr.shape[0]):
        m = jnp.full((g, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((g, 1), jnp.float32)
        acc = jnp.zeros((g, dh), jnp.float32)
        for j in range(t // bt):
            sl = slice(j * bt, (j + 1) * bt)
            m, l, acc = _tile_update_jit(
                qr[bh].astype(jnp.float32), kr[bh, sl], vr[bh, sl],
                ksr[bh, :, sl], vsr[bh, :, sl], j * bt, lens[bh], m, l, acc,
                window=window, scale=scale)
        rows.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
    return jnp.stack(rows).reshape(b, 1, h, dh)
