"""Jit'd public wrappers around the Pallas kernels.

Handles: shape padding to block multiples, >2-D activations (leading
dims are flattened into M), and a convenience ``QuantizedLinear`` record
the serving engine stores per weight matrix.

The leading-dim flattening is the serving batch contract (DESIGN.md §7):
``[B, S, K]`` — B requests packed by the batched engine — and ``[S, K]``
hit the identical kernel with rows computed independently, so batching
requests never changes a request's output bits (tests/test_kernels.py::
test_batch_rows_independent).

Trace economics (DESIGN.md §10): on the Pallas path the row count M is
padded up to the geometric ``bucketing.row_bucket`` ladder *before* the
jitted core, so the core's trace cache keys on the bucketed shape — any
two row counts in one bucket share a single trace/compile instead of one
per distinct M.  Zero-padding rows is invisible: each output row depends
only on its own input row, and the pad is sliced off on the way out.

Dispatch rule: a weight whose K and N are 128-aligned (every projection of
the registry's configs, K=896 int4 included) runs the Pallas kernel.  On a
TPU backend that kernel is compiled, and a shape it cannot tile raises
``ValueError``: the jnp reference never stands in for the device.  Off-TPU
the kernel bodies run under ``interpret=True`` (numerics identical, speed
irrelevant — tests assert allclose vs ref.py), and a misaligned test shape
falls back to the unpadded reference.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import qmm as _qmm
from . import quantize as _quantize
from . import ref as _ref
from .bucketing import row_bucket
from .pallas_env import on_tpu, use_interpret


def _kernel_tiles(k: int, n: int, n_groups: int) -> bool:
    """Can the quantized-matmul kernels tile a [K, N] weight with
    ``n_groups`` scale groups along K?"""
    return k % 128 == 0 and n % 128 == 0 and k % n_groups == 0


def _reference_off_tpu(what: str, shape) -> None:
    """Allow the jnp reference for a shape the kernel cannot tile, off
    TPU only; on a TPU backend raise instead."""
    if on_tpu():
        raise ValueError(
            f"{what}: shape {tuple(shape)} has no Pallas tiling (K and N "
            "must be multiples of 128, K of the group size); the TPU path "
            "has no reference fallback")


def _pick_block(dim: int, target: int, quantum: int) -> int:
    """Largest multiple of ``quantum`` <= target that divides ``dim``."""
    b = min(target, dim)
    b -= b % quantum
    while b > quantum and dim % b != 0:
        b -= quantum
    return max(b, quantum) if dim % quantum == 0 else dim


def _bucket_rows(xm: jax.Array):
    """Zero-pad a [M, K] activation's M up to its row bucket.

    Returns (xm [M_bucket, K], true row count).  Done *outside* the jitted
    cores so their trace caches key on the bucketed shape.
    """
    m0 = xm.shape[0]
    mp = row_bucket(m0)
    if mp != m0:
        xm = jnp.pad(xm, ((0, mp - m0), (0, 0)))
    return xm, m0


# the off-TPU reference matmuls for misaligned test shapes, jitted per
# exact shape (no row bucketing: padding would only waste compute)
_qmm_ref_jit = jax.jit(_ref.qmm_ref)
_qmm_int4_ref_jit = jax.jit(_ref.qmm_int4_ref)
_group_quantize_ref_jit = jax.jit(_ref.group_quantize_ref,
                                  static_argnames=("group_size", "bits"))
_group_quantize_jit = jax.jit(_quantize.group_quantize,
                              static_argnames=("group_size", "bits",
                                               "block_n", "interpret"))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def _qmm_core(xm: jax.Array, codes: jax.Array, scales: jax.Array,
              *, block_m: int, block_n: int, block_k: int,
              interpret: bool) -> jax.Array:
    """Pallas int8 core on a row-bucketed, block-aligned [M, K]."""
    k = xm.shape[-1]
    group = k // scales.shape[0]
    bk = _pick_block(k, block_k, max(group, 128))
    bn = _pick_block(codes.shape[1], block_n, 128)
    return _qmm.qmm(xm, codes, scales, block_m=min(block_m, xm.shape[0]),
                    block_n=bn, block_k=bk, interpret=interpret)


def quantized_matmul(x: jax.Array, codes: jax.Array, scales: jax.Array,
                     *, block_m: int = 256, block_n: int = 256,
                     block_k: int = 512,
                     interpret: bool | None = None) -> jax.Array:
    """x [..., K] @ dequant(codes [K, N], scales [K//G, N]) -> [..., N]."""
    interpret = use_interpret() if interpret is None else interpret
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = codes.shape[1]
    xm = x.reshape(-1, k)
    if not _kernel_tiles(k, n, scales.shape[0]):
        _reference_off_tpu("quantized_matmul", codes.shape)
        return _qmm_ref_jit(xm, codes, scales).reshape(*lead, n)
    xm, m0 = _bucket_rows(xm)
    # snap block_m to a 128-multiple divisor of the bucketed M so any
    # caller-chosen block size stays legal after row bucketing
    out = _qmm_core(xm, codes, scales,
                    block_m=_pick_block(xm.shape[0], block_m, 128),
                    block_n=block_n, block_k=block_k, interpret=interpret)
    return out[:m0].reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def _qmm_int4_core(xm: jax.Array, packed: jax.Array, scales: jax.Array,
                   *, block_m: int, block_n: int, block_k: int,
                   interpret: bool) -> jax.Array:
    """Pallas packed-int4 core on a row-bucketed, block-aligned [M, K].

    K tiles in multiples of 256 (so a packed block keeps 128-row
    multiples); a K that is not one (K=896) runs as a single full-K block."""
    k = xm.shape[-1]
    group = k // scales.shape[0]
    bk = _pick_block(k, block_k, max(group, 256))
    bn = _pick_block(packed.shape[1], block_n, 128)
    return _qmm.qmm_int4(xm, packed, scales,
                         block_m=min(block_m, xm.shape[0]),
                         block_n=bn, block_k=bk, interpret=interpret)


def quantized_matmul_int4(x: jax.Array, packed: jax.Array,
                          scales: jax.Array, *, block_m: int = 256,
                          block_n: int = 256, block_k: int = 512,
                          interpret: bool | None = None) -> jax.Array:
    """x [..., K] @ dequant(packed [K/2, N], scales) -> [..., N]."""
    interpret = use_interpret() if interpret is None else interpret
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = packed.shape[1]
    xm = x.reshape(-1, k)
    if not _kernel_tiles(k, n, scales.shape[0]):
        _reference_off_tpu("quantized_matmul_int4", (k, n))
        return _qmm_int4_ref_jit(xm, packed, scales).reshape(*lead, n)
    xm, m0 = _bucket_rows(xm)
    out = _qmm_int4_core(xm, packed, scales,
                         block_m=_pick_block(xm.shape[0], block_m, 128),
                         block_n=block_n, block_k=block_k,
                         interpret=interpret)
    return out[:m0].reshape(*lead, n)


def group_quantize(w: jax.Array, *, group_size: int = 128, bits: int = 8,
                   interpret: bool | None = None):
    """Fused quantizer; off TPU, falls back to the jnp reference for
    shapes the kernel cannot tile (on TPU those raise).

    Kernel path: K tiles into ``group_size`` groups and N is 128-aligned —
    the fused Pallas quantizer.  Off it, the reference quantizer runs with
    the largest group layout the shape admits:

    * ``k < group_size`` (or any k that still tiles into ``min(g, k)``) —
      one group spanning min(g, k) rows;
    * ``k`` not tileable at all — per-element groups (group_size 1), the
      degenerate layout where every code hits a quantization level exactly.
    """
    interpret = use_interpret() if interpret is None else interpret
    k, n = w.shape
    if k % group_size == 0 and n % 128 == 0:
        return _group_quantize_jit(w, group_size=group_size, bits=bits,
                                   block_n=_pick_block(n, 512, 128),
                                   interpret=interpret)
    _reference_off_tpu("group_quantize", w.shape)
    g = min(group_size, k)
    if k % g == 0:
        return _group_quantize_ref_jit(w, group_size=g, bits=bits)
    return _group_quantize_ref_jit(w, group_size=1, bits=bits)


# ---------------------------------------------------------------------------
# Serving-side weight record
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantizedLinear:
    """One HBM-resident quantized weight matrix (int8 or packed int4).

    ``bits`` is the *quantization* bit-width (1..8); the storage
    container follows from it — codes of <= 4 bits are nibble-packed two
    per byte (the int4 kernel dequantizes any code in [-7, 7]), wider
    codes are int8-resident.  This is the per-layer knob the
    mixed-precision serving plans turn (DESIGN.md §8).
    """

    codes: jax.Array            # int8 [K, N] or packed [K/2, N]
    scales: jax.Array           # f32 [K//G, N]
    bits: int                   # quantization bits, 1..8
    k: int                      # logical contraction dim

    def __matmul__(self, other):
        raise TypeError("use .apply(x)")

    def apply(self, x: jax.Array) -> jax.Array:
        if self.bits <= 4:
            return quantized_matmul_int4(x, self.codes, self.scales)
        return quantized_matmul(x, self.codes, self.scales)

    def nbytes(self) -> int:
        import numpy as np
        return (int(np.prod(self.codes.shape)) * self.codes.dtype.itemsize
                + int(np.prod(self.scales.shape)) * 4)


jax.tree_util.register_pytree_node(
    QuantizedLinear,
    lambda q: ((q.codes, q.scales), (q.bits, q.k)),
    lambda aux, ch: QuantizedLinear(ch[0], ch[1], aux[0], aux[1]),
)


def quantize_linear(w: jax.Array, *, bits: int = 8,
                    group_size: int = 128) -> QuantizedLinear:
    """Quantize one [K, N] weight for HBM residency.

    bits <= 4 quantizes at ``bits``-bit levels then packs two codes per
    byte along K (served by the int4 kernel); 5..8 stays int8-resident.
    """
    if not 1 <= bits <= 8:
        raise ValueError(f"kernel residency needs bits in 1..8, got {bits}")
    k = w.shape[0]
    codes, scales = group_quantize(w, group_size=group_size, bits=bits)
    if bits <= 4:
        return QuantizedLinear(codes=_ref.pack_int4_ref(codes),
                               scales=scales, bits=bits, k=k)
    return QuantizedLinear(codes=codes, scales=scales, bits=bits, k=k)
