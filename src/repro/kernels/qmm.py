"""Pallas TPU kernel: quantized-weight matmul with in-VMEM dequantization.

The paper's knob — agent-side weight bit-width b̂ — becomes, on TPU, an HBM
*bandwidth* knob: weights stay resident in HBM as int8 (or packed int4) and
are dequantized tile-by-tile in VMEM right before the MXU contraction.  For
the HBM-bound decode shapes this moves the memory roofline term by 2x (int8)
or 4x (int4) vs bf16 weights (see EXPERIMENTS.md §Perf).

Tiling (all MXU-aligned, multiples of 128 on M/N/K):

  grid = (M/bm, N/bn, K/bk)    K innermost -> sequential accumulation
  x tile      [bm, bk]  VMEM   (f32/bf16 activations)
  codes tile  [bk, bn]  VMEM   int8   (or [bk/2, bn] packed int4)
  scales tile [bk/G, 1, bn] VMEM f32   per-(group, out-channel), G | bk
  acc scratch [bm, bn]  VMEM   f32    (zeroed at k==0, flushed at k==K-1)

VMEM working set at defaults (bm=bn=256, bk=512, G=128):
  x 256*512*4 = 512 KiB, codes 512*256 = 128 KiB, scales 4*256*4 = 4 KiB,
  acc 256*256*4 = 256 KiB  ->  ~0.9 MiB of ~16 MiB VMEM.  Double-buffered
  inputs stay well under budget.

The scales ride as a [K/G, 1, N] view of the [K/G, N] array, so their
block's last two dims are (1, bn): the TPU lowering takes a block dim that
equals the array's, and a [bk/G, bn] block with bk/G < 8 rows (every K of
a 128-group model below 1024) is refused.

The kernel body is dtype-polymorphic; off-TPU tests validate it with
``interpret=True`` against ``ref.qmm_ref`` (see tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_env import use_interpret


def _dequant(codes, scales, group_size: int):
    """codes [bk, bn] * scales [bk//G, 1, bn] -> f32 [bk, bn].

    A grouped reshape + broadcast multiply: a layout-only expansion the
    compiler folds into the multiply, where jnp.repeat lowers to a VMEM
    gather."""
    bk, bn = codes.shape
    return (codes.astype(jnp.float32).reshape(bk // group_size, group_size, bn)
            * scales).reshape(bk, bn)


def _scales_spec(block_k: int, block_n: int, group_size: int):
    """BlockSpec of the [K//G, 1, N] scales view for grid (i, j, kk)."""
    return pl.BlockSpec((block_k // group_size, 1, block_n),
                        lambda i, j, kk: (kk, 0, j))


# ---------------------------------------------------------------------------
# int8 codes
# ---------------------------------------------------------------------------

def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int,
                group_size: int):
    """One (i, j, k) grid step: acc += x_tile @ dequant(w_tile)."""

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = w_ref[...]                                    # [bk, bn] int8
    w = _dequant(codes, s_ref[...], group_size)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def qmm(x: jax.Array, codes: jax.Array, scales: jax.Array, *,
        block_m: int = 256, block_n: int = 256, block_k: int = 512,
        interpret: "bool | None" = None) -> jax.Array:
    """x [M, K] @ dequant(codes [K, N], scales [K//G, N]) -> [M, N].

    Requires bm | M, bn | N, bk | K and G | bk (callers pad via ops.py).
    """
    interpret = use_interpret() if interpret is None else interpret
    m, k = x.shape
    k2, n = codes.shape
    assert k == k2, (k, k2)
    n_groups = scales.shape[0]
    assert k % n_groups == 0
    group_size = k // n_groups
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"unpadded shapes m={m} n={n} k={k} vs blocks "
        f"{block_m}/{block_n}/{block_k}")
    assert block_k % group_size == 0, (block_k, group_size)
    n_k = k // block_k

    kernel = functools.partial(_qmm_kernel, n_k=n_k, group_size=group_size)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            _scales_spec(block_k, block_n, group_size),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, codes, scales.reshape(n_groups, 1, n))


# ---------------------------------------------------------------------------
# packed int4 codes (two per byte along K)
# ---------------------------------------------------------------------------

def _qmm_int4_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int,
                     group_size: int):
    """Same contraction, but w_ref holds [bk/2, bn] packed int4 bytes that
    are unpacked (sign-extended) in VMEM before the dequant-matmul."""

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = w_ref[...].astype(jnp.int32)                 # [bk/2, bn]
    lo = packed & 0x0F
    hi = (packed >> 4) & 0x0F
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    bk2, bn = packed.shape
    bk = 2 * bk2
    codes = jnp.stack([lo, hi], axis=1).reshape(bk, bn)       # [bk, bn]
    w = _dequant(codes, s_ref[...], group_size)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def qmm_int4(x: jax.Array, packed: jax.Array, scales: jax.Array, *,
             block_m: int = 256, block_n: int = 256, block_k: int = 512,
             interpret: "bool | None" = None) -> jax.Array:
    """x [M, K] @ dequant(packed [K/2, N] int4x2, scales [K//G, N])."""
    interpret = use_interpret() if interpret is None else interpret
    m, k = x.shape
    k2, n = packed.shape
    assert k == 2 * k2, (k, k2)
    n_groups = scales.shape[0]
    group_size = k // n_groups
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    assert block_k % 2 == 0 and block_k % group_size == 0
    n_k = k // block_k

    kernel = functools.partial(_qmm_int4_kernel, n_k=n_k,
                               group_size=group_size)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k // 2, block_n), lambda i, j, kk: (kk, j)),
            _scales_spec(block_k, block_n, group_size),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, packed, scales.reshape(n_groups, 1, n))
