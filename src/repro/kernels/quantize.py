"""Pallas TPU kernel: fused group quantizer (absmax -> scale -> round -> clip).

One pass over the weight matrix produces int8 codes + per-(group, column)
scales without materializing any f32 intermediate in HBM.  This is the
kernel the serving engine runs once at model-load time (and the QAT path
runs per-step on the agent partition), so weights go HBM-resident in low
precision immediately.

Tiling: grid = (K/G, N/bn); each step owns one [G, bn] group tile in VMEM,
reduces absmax over the group axis, writes [G, bn] int8 codes and [1, bn]
f32 scales.  G is the quantization group size (default 128 — one MXU lane
tile), bn defaults to 512 -> ~320 KiB VMEM per step.  The scales are
written through a [K/G, 1, N] view so each block's last two dims are
(1, bn), a shape the TPU lowering accepts (a (1, bn) block of the
[K/G, N] array is not).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_env import use_interpret


def _group_quant_kernel(w_ref, codes_ref, scale_ref, *, levels: int):
    w = w_ref[...].astype(jnp.float32)                     # [G, bn]
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)      # [1, bn]
    scale = jnp.where(amax > 0, amax / levels, 1.0)
    q = jnp.clip(jnp.round(w / scale), -levels, levels)
    codes_ref[...] = q.astype(jnp.int8)
    scale_ref[0] = scale


def group_quantize(w: jax.Array, *, group_size: int = 128, bits: int = 8,
                   block_n: int = 512, interpret: "bool | None" = None):
    """w [K, N] float -> (codes int8 [K, N], scales f32 [K//G, N]).

    Symmetric uniform quantization, matching
    ``repro.core.quantization.quantize`` at per-group granularity and
    ``ref.group_quantize_ref`` exactly.
    """
    interpret = use_interpret() if interpret is None else interpret
    k, n = w.shape
    assert k % group_size == 0, (k, group_size)
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    levels = 2 ** (bits - 1) - 1

    kernel = functools.partial(_group_quant_kernel, levels=levels)
    codes, scales = pl.pallas_call(
        kernel,
        grid=(k // group_size, n // block_n),
        in_specs=[pl.BlockSpec((group_size, block_n),
                               lambda g, j: (g, j))],
        out_specs=[
            pl.BlockSpec((group_size, block_n), lambda g, j: (g, j)),
            pl.BlockSpec((1, 1, block_n), lambda g, j: (g, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), jnp.int8),
            jax.ShapeDtypeStruct((k // group_size, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(w)
    return codes, scales.reshape(k // group_size, n)


# ---------------------------------------------------------------------------
# KV-cache quantization (decode serving, DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# The decode engine stores each cache entry as int8-held codes + one f32
# scale per head vector: absmax over the trailing head_dim axis, the same
# scale/round/clip rule as ``_group_quant_kernel`` (so the weight and
# cache quantizers share arithmetic).  These are plain jnp functions, not
# pallas_call kernels: they are traced *into* the AOT-compiled decode
# step, where XLA fuses the dequantize into the attention reads — a
# separate kernel launch per step would cost more than it saves at
# decode's [B, 1] arithmetic intensity.

def kv_levels(bits: int) -> int:
    """Symmetric code magnitude at ``bits`` (7 for int4, 127 for int8)."""
    return 2 ** (bits - 1) - 1


def kv_quantize(x: jax.Array, bits: int):
    """x [..., head_dim] float -> (codes int8 [...], scales f32 [... minus last]).

    Absmax granularity is one scale per head vector (the trailing axis),
    i.e. per (layer, row, position, kv_head) for a [L, B, T, KV, dh]
    cache block.  Zero vectors quantize to scale 1.0 / codes 0, so
    padded cache positions round-trip harmlessly.
    """
    levels = kv_levels(bits)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)                   # [...]
    scale = jnp.where(amax > 0, amax / levels, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -levels, levels)
    return q.astype(jnp.int8), scale


def kv_dequantize(codes: jax.Array, scales: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """Inverse map: codes [..., dh] int8, scales [...] -> float [..., dh]."""
    return (codes.astype(jnp.float32) * scales[..., None]).astype(dtype)


def kv_cache_bytes(shape, bits: int, *, scale_bytes: int = 4) -> int:
    """Stored size of a quantized [..., head_dim] cache block.

    Codes are billed at the realizable container (int4 nibble-packed for
    <= 4 bits, int8 for 5..8 — ``core.quantization.wire_bytes``), plus
    one f32 scale per head vector.  A >= 16-bit cache is stored raw
    (2-byte entries, no scales).
    """
    from repro.core.quantization import wire_bytes
    n = 1
    for d in shape:
        n *= int(d)
    if bits >= 16:
        return 2 * n
    n_vec = n // int(shape[-1])
    return wire_bytes(n, bits) + scale_bytes * n_vec
