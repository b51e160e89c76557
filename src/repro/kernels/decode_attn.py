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
``[G, dh]`` query group, kv tiles innermost.  The kernel reads the
decode engine's whole head-major cache stack where it lies: codes
``[L, B, KV, dh, T]`` and per-position scales ``[L, B, KV, 1, T]`` fold
to ``[L, B * KV, ...]`` by a reshape (a bitcast), and the layer index is
a scalar-prefetch operand beside each program's cache length, so the
tiles' index maps pick ``(layer, row, tile)`` and no per-layer slice or
head transpose of the cache exists (DESIGN.md §13).  The kernel also
makes the step's one write: the program whose tile holds a row's newest
position puts the row's new entry into that tile in VMEM before
attending over it, and copies the tile back into the cache, whose
operands alias outputs left in HBM.  The online-softmax
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

    q [G, dh] f32; k/v codes [dh, bt] (int8 or float; positions along
    lanes, as the cache lies); scales [1, bt] f32 (one per cache
    position, along lanes); ``cache_len`` a scalar; m/l
    [G, 1], acc [G, dh] f32 running state.  Returns the updated
    (m, l, acc).  Each position's scale multiplies its score column
    (``q . (c s) = (q . c) s``) and its probability column
    (``sum_t p_t s_t v_t``), so the scales never leave the lane axis.
    Shared by the Pallas kernel body (on VMEM refs) and the jnp reference
    (on array slices): identical ops, identical bits.
    """
    bt = k_codes.shape[1]
    g = q.shape[0]
    k = k_codes.astype(jnp.float32)
    v = v_codes.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
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
        p * v_scales, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


# Interpret-mode pallas evaluates the kernel body as a jitted
# sub-computation per grid step; the reference must run each tile through
# jit the same way, or XLA's within-tile fusion (fma contraction in the
# l/acc updates) drifts the accumulators by a few ULPs once a second tile
# feeds a nonzero carry.  Single jit cache entry per (window, scale).
_tile_update_jit = jax.jit(_tile_update, static_argnames=("window", "scale"))


def _qdecode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref,
                    vs_ref, kn_ref, vn_ref, ksn_ref, vsn_ref, o_ref, ko_ref,
                    vo_ref, kso_ref, vso_ref, acc_ref, m_ref, l_ref, sem, *,
                    n_t: int, bt: int, window: int, scale: float):
    bh, j = pl.program_id(0), pl.program_id(1)
    cache_len = len_ref[bh]
    at = _entry_pos(cache_len, n_t * bt)
    # the stack's tiles arrive as [1, 1, X, bt] blocks
    first = (0, 0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j == at // bt)
    def _write():
        # the new entry goes into its tile in VMEM, so this step attends
        # over it, and that tile alone goes back to the cache
        col = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) == at - j * bt
        copies = []
        for i, (tile_ref, new_ref, out_ref) in enumerate((
                (k_ref, kn_ref, ko_ref), (v_ref, vn_ref, vo_ref),
                (ks_ref, ksn_ref, kso_ref), (vs_ref, vsn_ref, vso_ref))):
            tile_ref[first] = jnp.where(col, new_ref[0], tile_ref[first])
            if n_t == 1:                    # the row's one tile: a block
                out_ref[...] = tile_ref[...]
                continue
            span = pl.ds(pl.multiple_of(j * bt, bt), bt)
            dst = out_ref.at[pl.ds(layer_ref[0], 1), pl.ds(bh, 1), :, span]
            copies.append(pltpu.make_async_copy(tile_ref, dst, sem.at[i]))
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    m, l, acc = _tile_update(
        q_ref[0].astype(jnp.float32), k_ref[first], v_ref[first],
        ks_ref[first], vs_ref[first], j * bt, cache_len, m_ref[...],
        l_ref[...], acc_ref[...], window=window, scale=scale)
    m_ref[...] = m
    l_ref[...] = l
    acc_ref[...] = acc

    @pl.when(j == n_t - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _entry_pos(cache_len, t: int):
    """Where a step's new entry lies: the last live position, clamped
    into the bucket as ``dynamic_update_slice`` clamps (a dead slot's
    length keeps counting past it)."""
    return jnp.clip(cache_len - 1, 0, t - 1)


def cache_layout(codes, scales):
    """Quantized entries as ``kv_quantize`` returns them — codes
    [..., T, KV, dh], scales [..., T, KV] — in the cache's layout:
    codes [..., KV, dh, T] and scales [..., KV, 1, T], head-major with
    positions minor, the layout the TPU gives such an array anyway (it
    puts the longer of the two minor axes last)."""
    n = codes.ndim
    codes = codes.transpose(tuple(range(n - 3)) + (n - 2, n - 1, n - 3))
    return codes, scales.swapaxes(-1, -2)[..., None, :]


def _fold(q, k_codes, v_codes, k_scales, v_scales, cache_len):
    """Cache layouts -> the kernel's GQA-folded [B*KV, ...] views.

    The cache is head-major, so merging (B, KV) into one program axis is
    a reshape of adjacent axes (a bitcast, never a transpose): codes
    [L, B*KV, dh, T], scales [L, B*KV, 1, T], whose tile block (1, bt)
    keeps the leading 1 equal to the array's.  The query folds to
    [B*KV, G, dh]; lengths to one int32 per program, read from SMEM."""
    b, _, h, dh = q.shape
    n_l, _, kv, _, t = k_codes.shape
    qr = q.reshape(b * kv, h // kv, dh)
    stack = tuple(c.reshape(n_l, b * kv, c.shape[3], t)
                  for c in (k_codes, v_codes, k_scales, v_scales))
    lens = jnp.broadcast_to(jnp.reshape(cache_len, (-1, 1)), (b, kv))
    return qr, stack, lens.astype(jnp.int32).reshape(b * kv)


def quantized_decode_attention(q, k_codes, v_codes, k_scales, v_scales,
                               cache_len, layer, entries, *,
                               window: int = 0, block_t: int = 128,
                               interpret: "bool | None" = None):
    """One decode step's attention for one layer of a quantized cache
    stack: write the step's new entries where they lie, then attend.

    q [B, 1, H, dh]; codes [L, B, KV, dh, T] (int8 codes, or the raw
    float container for b_kv >= 16); scales [L, B, KV, 1, T] f32 (ones
    for raw); cache_len [] or [B]; ``layer`` a scalar (traced or not)
    naming the layer; ``entries`` the step's new (k codes [B, KV, dh],
    v codes, k scales [B, KV], v scales).  Each row's entry is written
    at position ``cache_len - 1`` of ``layer`` (clamped into the
    bucket) and attended over in the same step.  The layer index is a
    scalar-prefetch operand, so the tiles' index maps pick
    ``(layer, row, tile)`` out of the whole stack and no per-layer slice
    is ever materialized; the cache operands alias the outputs, and only
    the one tile per row that holds the entry is copied back.

    Returns ``(out, (k_codes, v_codes, k_scales, v_scales))``: out
    [B, 1, H, dh] in q.dtype — the ``layers.decode_attention`` contract,
    minus the dequantized-cache intermediate — and the updated stack.
    T must be a multiple of the tile size ``min(block_t, T)`` (cache
    buckets are 16·2^k, so it always is).
    """
    interpret = use_interpret() if interpret is None else interpret
    b, _, h, dh = q.shape
    kv, t = k_codes.shape[2], k_codes.shape[4]
    g = h // kv
    bt = min(block_t, t)
    assert t % bt == 0, (t, bt)
    n_t = t // bt
    qr, stack, lens = _fold(q, k_codes, v_codes, k_scales, v_scales,
                            cache_len)
    at = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # new entries as [B*KV, X, 1] columns (X = dh, or 1 for scales):
    # positions are the cache's minor axis, so an entry is a tile column
    new = tuple(e.astype(c.dtype).reshape(b * kv, c.shape[3], 1)
                for e, c in zip(entries, (k_codes, v_codes, k_scales,
                                          v_scales)))

    kernel = functools.partial(_qdecode_kernel, n_t=n_t, bt=bt,
                               window=window, scale=dh ** -0.5)
    # index maps take the prefetched lengths and layer as trailing
    # arguments
    row = lambda bh, j, lens, at: (bh, 0, 0)
    tile = lambda bh, j, lens, at: (at[0], bh, 0, j)
    blocks = [c.shape[2] for c in stack]            # dh, dh, 1, 1
    # the stack's outputs stay in HBM and take the one written tile by a
    # copy, so no grid step pays for output blocks it does not write;
    # a one-tile bucket (under 128 positions, where a lane slice of the
    # HBM row cannot be 128-aligned) writes its row as a block instead
    out_stack = ([pl.BlockSpec((1, 1, x, t), tile) for x in blocks]
                 if n_t == 1 else [pl.BlockSpec(memory_space=pl.ANY)] * 4)
    outs = pl.pallas_call(
        kernel,
        name="decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * kv, n_t),
            in_specs=[pl.BlockSpec((1, g, dh), row)]
            + [pl.BlockSpec((1, 1, x, bt), tile) for x in blocks]
            + [pl.BlockSpec((1, x, 1), row) for x in blocks],
            out_specs=[pl.BlockSpec((1, g, dh), row)] + out_stack,
            scratch_shapes=[
                pltpu.VMEM((g, dh), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((4,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((b * kv, g, dh), q.dtype)]
        + [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in stack],
        # operands 3..6 (after lens, layer and q) are the cache stack
        input_output_aliases={3 + i: 1 + i for i in range(4)},
        interpret=interpret,
    )(lens, at, qr, *stack, *new)
    return outs[0].reshape(b, 1, h, dh), tuple(
        a.reshape(c.shape) for a, c in zip(
            outs[1:], (k_codes, v_codes, k_scales, v_scales)))


def quantized_decode_attention_ref(q, k_codes, v_codes, k_scales, v_scales,
                                   cache_len, layer, entries, *,
                                   window: int = 0, block_t: int = 128):
    """Pure-jnp oracle running the kernel's exact tile schedule.

    Same contract as :func:`quantized_decode_attention`: the entries are
    written into the stack first (functionally), then Python loops over
    (row·kv-head) programs and kv tiles of the layer, each tile
    evaluated through the *same* :func:`_tile_update` the kernel body
    calls, jitted per tile exactly as interpret mode executes the kernel
    body — so reference and kernel run the identical compiled tile
    computation and match bitwise (``tests/test_decode_kernel.py``
    asserts it per b_kv rung).
    """
    b, _, h, dh = q.shape
    t = k_codes.shape[4]
    bt = min(block_t, t)
    assert t % bt == 0, (t, bt)
    pos = _entry_pos(jnp.broadcast_to(jnp.reshape(cache_len, (-1,)), (b,)),
                     t)
    stack = []
    for c, e in zip((k_codes, v_codes, k_scales, v_scales), entries):
        e = e.astype(c.dtype).reshape(b, c.shape[2], c.shape[3])
        for r in range(b):
            c = c.at[layer, r, :, :, pos[r]].set(e[r])
        stack.append(c)
    qr, views, lens = _fold(q, *stack, cache_len)
    kr, vr, ksr, vsr = (v[layer] for v in views)
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
                qr[bh].astype(jnp.float32), kr[bh, :, sl], vr[bh, :, sl],
                ksr[bh, :, sl], vsr[bh, :, sl], j * bt, lens[bh], m, l, acc,
                window=window, scale=scale)
        rows.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
    return jnp.stack(rows).reshape(b, 1, h, dh), tuple(stack)
