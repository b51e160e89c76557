"""Pure-jnp oracles for the Pallas kernels (the allclose targets in tests).

Shapes / conventions shared with qmm.py and quantize.py:

  x       [M, K]            activations (f32 or bf16)
  codes   [K, N]  int8      quantized weights (int4 values live in [-7, 7])
  scales  [K // G, N] f32   per-(group, out-channel) scales, group size G
                            along the contraction axis
  out     [M, N]            x @ (codes * scales)

``group_quantize_ref`` is the oracle for the fused quantizer kernel:
symmetric absmax scaling per (group, column), matching
``repro.core.quantization`` with scheme='uniform', granularity='per-group'.
``decode_attention_ref`` is the oracle for the quantized decode attention
(``decode_attn.py``): it dequantizes the whole cache and attends at once,
sharing nothing with the kernel's tiled schedule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dequantize_ref(codes: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """[K, N] int8 codes + [K//G, N] scales -> [K, N] f32 weights."""
    k = codes.shape[0]
    g = k // scales.shape[0]
    s_full = jnp.repeat(scales, g, axis=0)
    return codes.astype(jnp.float32) * s_full


def qmm_ref(x: jnp.ndarray, codes: jnp.ndarray,
            scales: jnp.ndarray) -> jnp.ndarray:
    """Oracle for the quantized matmul: dequantize then matmul in f32."""
    w = dequantize_ref(codes, scales)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def group_quantize_ref(w: jnp.ndarray, group_size: int, bits: int = 8):
    """Oracle for the fused group quantizer.

    w: [K, N] float.  Returns (codes int8 [K, N], scales f32 [K//G, N]).
    Symmetric: scale = absmax / (2^(bits-1) - 1), codes = round(w / scale).
    """
    k, n = w.shape
    assert k % group_size == 0, (k, group_size)
    levels = 2 ** (bits - 1) - 1
    wg = w.reshape(k // group_size, group_size, n).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wg), axis=1)                      # [K//G, N]
    scales = jnp.where(amax > 0, amax / levels, 1.0)
    codes = jnp.clip(jnp.round(wg / scales[:, None, :]), -levels, levels)
    return codes.reshape(k, n).astype(jnp.int8), scales


def unpack_int4_ref(packed: jnp.ndarray) -> jnp.ndarray:
    """[K//2, N] packed (two 4-bit codes per byte along K) -> [K, N] int8.

    Layout: byte b at row r holds code[2r] in the low nibble, code[2r+1] in
    the high nibble, two's complement.
    """
    lo = packed & 0x0F
    hi = (packed >> 4) & 0x0F
    lo = jnp.where(lo >= 8, lo - 16, lo).astype(jnp.int8)
    hi = jnp.where(hi >= 8, hi - 16, hi).astype(jnp.int8)
    k2, n = packed.shape
    out = jnp.stack([lo, hi], axis=1)           # [K//2, 2, N]
    return out.reshape(2 * k2, n)


def pack_int4_ref(codes: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`unpack_int4_ref` — [K, N] int8 in [-7,7] ->
    [K//2, N] packed bytes."""
    k, n = codes.shape
    assert k % 2 == 0
    c = codes.reshape(k // 2, 2, n)
    lo = c[:, 0].astype(jnp.int32) & 0x0F
    hi = (c[:, 1].astype(jnp.int32) & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def qmm_int4_ref(x: jnp.ndarray, packed: jnp.ndarray,
                 scales: jnp.ndarray) -> jnp.ndarray:
    """Oracle for the int4-packed matmul (unpack along K, then qmm)."""
    codes = unpack_int4_ref(packed)
    return qmm_ref(x, codes, scales)


def decode_attention_ref(q, k_codes, v_codes, k_scales, v_scales,
                         cache_len, *, window: int = 0):
    """Oracle for the quantized decode attention: dequantize the whole
    cache, then one masked softmax per query head.

    q [B, 1, H, dh]; codes [B, T, KV, dh]; scales [B, T, KV]; cache_len
    [B] (positions at or beyond it are masked, and with ``window`` > 0
    so are those more than ``window`` back).  Returns [B, 1, H, dh] f32.
    """
    b, _, h, dh = q.shape
    t, kv = k_codes.shape[1], k_codes.shape[2]
    k = k_codes.astype(jnp.float32) * k_scales[..., None]
    v = v_codes.astype(jnp.float32) * v_scales[..., None]
    # GQA: query head i reads KV head i // (H / KV)
    k = jnp.repeat(k, h // kv, axis=2)                   # [B, T, H, dh]
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q[:, 0].astype(jnp.float32), k) \
        * dh ** -0.5
    pos = jnp.arange(t)[None, None, :]
    n = jnp.reshape(cache_len, (-1, 1, 1))
    valid = pos < n
    if window > 0:
        valid &= pos >= n - window
    p = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
    return jnp.einsum("bht,bthd->bhd", p, v)[:, None]
