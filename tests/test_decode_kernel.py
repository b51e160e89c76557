"""The fused dequant-attend decode kernel vs its shared reference.

``quantized_decode_attention`` (kernels/decode_attn.py) is the decode
engine's attention primitive: it reads int8-held KV codes + per-vector
scales straight from the cache and dequantizes per-tile in VMEM.  The
house bitwise-parity invariant extends down to it:
``quantized_decode_attention_ref`` — the plain-Python oracle built on
the SAME per-tile update — must match the kernel bit for bit, across
stored bit-widths, head shapes, cache buckets, tile widths, and sliding
windows; and cache-bucket padding must be invisible to the outputs.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn import (quantized_decode_attention,
                                       quantized_decode_attention_ref)
from repro.kernels.quantize import kv_quantize
from repro.kernels.ref import decode_attention_ref


def _case(b, h, kv, dh, t, b_kv, seed=0):
    """Random [B, 1, H, dh] query + quantized [B, T, KV, dh] cache with
    ragged per-row lengths (every row shorter than the bucket)."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, 1, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kv, dh)), jnp.float32)
    if b_kv < 16:
        kc, ks = kv_quantize(k, b_kv)
        vc, vs = kv_quantize(v, b_kv)
    else:
        kc, vc = k, v
        ks = jnp.ones(k.shape[:-1], jnp.float32)
        vs = jnp.ones(v.shape[:-1], jnp.float32)
    lens = jnp.asarray(rng.integers(1, t + 1, size=b), jnp.int32)
    return q, kc, vc, ks, vs, lens


# the ladder the engine actually serves: every stored bit-width times a
# head-dim / cache-bucket grid covering single- and multi-tile grids
LADDER = [(dh, t, bt)
          for dh in (8, 16, 32)
          for (t, bt) in ((16, 16), (64, 16), (128, 32))]


@pytest.mark.parametrize("b_kv", [4, 8, 16])
@pytest.mark.parametrize("dh,t,bt", LADDER)
def test_kernel_matches_reference_bitwise(b_kv, dh, t, bt):
    q, kc, vc, ks, vs, lens = _case(2, 4, 2, dh, t, b_kv,
                                    seed=dh * 1000 + t + b_kv)
    out = quantized_decode_attention(q, kc, vc, ks, vs, lens, block_t=bt)
    want = quantized_decode_attention_ref(q, kc, vc, ks, vs, lens,
                                          block_t=bt)
    assert np.array_equal(np.asarray(out), np.asarray(want)), (
        f"b_kv={b_kv} dh={dh} t={t} bt={bt}: kernel diverged from the "
        "shared reference")


@pytest.mark.parametrize("b_kv", [4, 8])
@pytest.mark.parametrize("window", [3, 7])
def test_kernel_matches_reference_sliding_window(b_kv, window):
    q, kc, vc, ks, vs, lens = _case(2, 4, 2, 16, 64, b_kv, seed=window)
    out = quantized_decode_attention(q, kc, vc, ks, vs, lens,
                                     window=window, block_t=16)
    want = quantized_decode_attention_ref(q, kc, vc, ks, vs, lens,
                                          window=window, block_t=16)
    assert np.array_equal(np.asarray(out), np.asarray(want))


def test_gqa_head_fold():
    """H query heads sharing KV groups: folding [B, 1, H, dh] into
    (B*KV, G, dh) kernel rows must keep each group's queries attending
    its own KV stream — checked against the whole-cache oracle of
    kernels/ref.py."""
    b, h, kv, dh, t = 2, 8, 2, 16, 32
    q, kc, vc, ks, vs, lens = _case(b, h, kv, dh, t, 8, seed=3)
    out = quantized_decode_attention(q, kc, vc, ks, vs, lens, block_t=16)
    want = decode_attention_ref(q, kc, vc, ks, vs, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("grow", [16, 96])
def test_cache_bucket_padding_is_attention_invisible(grow):
    """Growing the cache bucket around identical live entries must not
    change the output by a single bit: padded tiles are fully masked,
    and a fully-masked tile's online-softmax update is an exact no-op
    (the hypothesis-driven version lives in test_properties.py)."""
    t = 32
    q, kc, vc, ks, vs, lens = _case(2, 4, 2, 16, t, 8, seed=grow)
    pad = [(0, 0), (0, grow), (0, 0), (0, 0)]
    out = quantized_decode_attention(q, kc, vc, ks, vs, lens, block_t=16)
    out_pad = quantized_decode_attention(
        q, jnp.pad(kc, pad), jnp.pad(vc, pad),
        jnp.pad(ks, pad[:-1]), jnp.pad(vs, pad[:-1]), lens, block_t=16)
    assert np.array_equal(np.asarray(out), np.asarray(out_pad))


def test_raw_16bit_container_is_exact():
    """b_kv >= 16 stores the raw cache with ones scales through the same
    kernel: dequantization is then x * 1.0, so the quantized path must
    equal unquantized flash-decoding exactly."""
    rng = np.random.default_rng(9)
    b, h, kv, dh, t = 2, 4, 2, 16, 32
    q = jnp.asarray(rng.standard_normal((b, 1, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kv, dh)), jnp.float32)
    ones = jnp.ones(k.shape[:-1], jnp.float32)
    lens = jnp.asarray([t, t // 2], jnp.int32)
    out = quantized_decode_attention(q, k, v, ones, ones, lens,
                                     block_t=16)
    want = quantized_decode_attention(q, k * 1.0, v * 1.0, ones, ones,
                                      lens, block_t=16)
    assert np.array_equal(np.asarray(out), np.asarray(want))
    assert np.isfinite(np.asarray(out)).all()
