"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per the assignment: sweep shapes/dtypes and assert_allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # soft dep: skips property tests when absent

from repro.kernels import ops, ref

SHAPES = [
    (128, 256, 128, 128),     # M, K, N, G — minimal aligned
    (256, 512, 256, 128),
    (64, 1024, 384, 256),     # non-square, bigger groups
    (512, 256, 128, 64),      # small group
    (1, 896, 4864, 128),      # decode row  x  qwen2 MLP
    (7, 512, 256, 128),       # ragged M (padding path)
    (33, 640, 256, 128),      # K not multiple of default block_k
]

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,k,n,g", SHAPES)
def test_qmm_int8_matches_ref(m, k, n, g):
    dtype = jnp.float32
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 7 + k))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    codes, scales = ref.group_quantize_ref(w, g)
    out = ops.quantized_matmul(x, codes, scales)
    want = ref.qmm_ref(x, codes, scales)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
def test_qmm_dtype_sweep(dtype):
    m, k, n, g = 128, 512, 256, 128
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    codes, scales = ref.group_quantize_ref(w, g)
    out = ops.quantized_matmul(x, codes, scales)
    assert out.dtype == dtype
    want = ref.qmm_ref(x, codes, scales)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("m,k,n,g", SHAPES)
def test_qmm_int4_matches_ref(m, k, n, g):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    codes, scales = ref.group_quantize_ref(w, g, bits=4)
    packed = ref.pack_int4_ref(codes)
    out = ops.quantized_matmul_int4(x, packed, scales)
    want = ref.qmm_int4_ref(x, packed, scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("k,n,g,bits", [
    (256, 128, 128, 8), (512, 256, 64, 8), (1024, 384, 256, 8),
    (256, 128, 128, 4), (512, 512, 128, 4),
])
def test_group_quantize_matches_ref(k, n, g, bits):
    w = jax.random.normal(jax.random.PRNGKey(k + n), (k, n))
    codes, scales = ops.group_quantize(w, group_size=g, bits=bits)
    codes_r, scales_r = ref.group_quantize_ref(w, g, bits=bits)
    assert bool(jnp.all(codes == codes_r))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales_r),
                               rtol=1e-6)


def test_group_quantize_fallback_k_smaller_than_group():
    """k < group_size (and n 128-misaligned doesn't matter): one group
    spanning the whole contraction axis."""
    w = jax.random.normal(jax.random.PRNGKey(0), (96, 128))
    codes, scales = ops.group_quantize(w, group_size=128)
    codes_r, scales_r = ref.group_quantize_ref(w, 96)
    assert scales.shape == (1, 128)
    assert bool(jnp.all(codes == codes_r))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales_r),
                               rtol=1e-6)


def test_group_quantize_fallback_k_not_tileable():
    """k >= group_size but k % group_size != 0: degenerates to per-element
    groups (group_size 1) — every code then sits exactly on a level."""
    w = jax.random.normal(jax.random.PRNGKey(1), (192, 128))
    codes, scales = ops.group_quantize(w, group_size=128)
    codes_r, scales_r = ref.group_quantize_ref(w, 1)
    assert scales.shape == (192, 128)
    assert bool(jnp.all(codes == codes_r))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales_r),
                               rtol=1e-6)
    # per-element quantization is exact: dequant reproduces w (where w!=0)
    np.testing.assert_allclose(
        np.asarray(codes, np.float32) * np.asarray(scales), np.asarray(w),
        rtol=1e-5, atol=1e-6)


def test_group_quantize_fallback_n_misaligned():
    """k tiles but n % 128 != 0: the reference quantizer runs with the
    requested group size (the Pallas fast path needs 128-aligned N)."""
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 100))
    codes, scales = ops.group_quantize(w, group_size=128, bits=4)
    codes_r, scales_r = ref.group_quantize_ref(w, 128, bits=4)
    assert scales.shape == (2, 100)
    assert bool(jnp.all(codes == codes_r))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales_r),
                               rtol=1e-6)


def test_quantized_matmul_row_bucket_padding_invisible():
    """ops.py pads M to the geometric row ladder outside the jitted core;
    any two row counts in one bucket share a trace and every real row's
    bits are unchanged by the pad."""
    from repro.kernels.bucketing import row_bucket
    k, n, g = 256, 128, 128
    w = jax.random.normal(jax.random.PRNGKey(3), (k, n))
    codes, scales = ref.group_quantize_ref(w, g)
    x = jax.random.normal(jax.random.PRNGKey(4), (300, k))
    assert row_bucket(300) == 512
    out = ops.quantized_matmul(x, codes, scales)
    assert out.shape == (300, n)
    for m in (1, 130, 300):
        sub = ops.quantized_matmul(x[:m], codes, scales)
        np.testing.assert_array_equal(np.asarray(sub),
                                      np.asarray(out[:m]))


def test_pack_unpack_int4_roundtrip():
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(-7, 8, (256, 128)), jnp.int8)
    packed = ref.pack_int4_ref(codes)
    assert packed.shape == (128, 128)
    out = ref.unpack_int4_ref(packed)
    assert bool(jnp.all(out == codes))


def test_leading_dims_flattened():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 256))
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 128))
    codes, scales = ops.group_quantize(w, group_size=128)
    out = ops.quantized_matmul(x, codes, scales)
    assert out.shape == (4, 8, 128)
    want = ref.qmm_ref(x.reshape(-1, 256), codes, scales).reshape(4, 8, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_leading_dims_flattened_int4():
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 2, 8, 256))
    w = jax.random.normal(jax.random.PRNGKey(6), (256, 128))
    codes, scales = ref.group_quantize_ref(w, 128, bits=4)
    packed = ref.pack_int4_ref(codes)
    out = ops.quantized_matmul_int4(x, packed, scales)
    assert out.shape == (3, 2, 8, 128)
    want = ref.qmm_int4_ref(x.reshape(-1, 256), packed,
                            scales).reshape(3, 2, 8, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bits", [8, 4])
def test_batch_rows_independent(bits):
    """Serving invariant (DESIGN.md §7): each batch row's result equals the
    row served alone — bitwise, so batching requests never changes
    per-request logits."""
    b, s, k, n = 5, 16, 256, 128
    x = jax.random.normal(jax.random.PRNGKey(7), (b, s, k))
    w = jax.random.normal(jax.random.PRNGKey(8), (k, n))
    ql = ops.quantize_linear(w, bits=bits)
    batched = np.asarray(ql.apply(x))
    for i in range(b):
        single = np.asarray(ql.apply(x[i:i + 1]))
        np.testing.assert_array_equal(batched[i], single[0])


def test_quantize_linear_end_to_end_error_scales_with_bits():
    """int4 residency must cost more accuracy than int8 — and both must be
    within the analytic per-group error bound."""
    k, n = 512, 256
    x = jax.random.normal(jax.random.PRNGKey(3), (32, k))
    w = jax.random.normal(jax.random.PRNGKey(4), (k, n))
    exact = x @ w
    err8 = float(jnp.mean(jnp.abs(
        ops.quantize_linear(w, bits=8).apply(x) - exact)))
    err4 = float(jnp.mean(jnp.abs(
        ops.quantize_linear(w, bits=4).apply(x) - exact)))
    assert err8 < err4 < 10 * err8 * 16 + 1.0
    assert err8 < 0.05 * float(jnp.mean(jnp.abs(exact)))


@settings(max_examples=10, deadline=None)
@given(mm=st.sampled_from([1, 3, 64, 128]),
       kk=st.sampled_from([256, 512]),
       nn=st.sampled_from([128, 384]),
       seed=st.integers(0, 1000))
def test_prop_qmm_random_shapes(mm, kk, nn, seed):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (mm, kk))
    w = jax.random.normal(kw, (kk, nn))
    codes, scales = ref.group_quantize_ref(w, 128)
    np.testing.assert_allclose(
        np.asarray(ops.quantized_matmul(x, codes, scales)),
        np.asarray(ref.qmm_ref(x, codes, scales)), rtol=1e-4, atol=1e-4)


def test_int4_k896_dispatches_to_kernel(monkeypatch):
    """qwen2-0.5b's K=896 projections (896 = 7 x 128, not a multiple of
    256) run the int4 Pallas kernel as one full-K block — never the jnp
    reference, which would hide the device on a TPU."""
    def no_reference(*a, **kw):
        raise AssertionError("K=896 int4 fell back to the jnp reference")

    monkeypatch.setattr(ops, "_qmm_int4_ref_jit", no_reference)
    kx, kw = jax.random.split(jax.random.PRNGKey(896))
    x = jax.random.normal(kx, (2, 3, 896))
    w = jax.random.normal(kw, (896, 128))
    ql = ops.quantize_linear(w, bits=4)
    out = ql.apply(x)
    want = ref.qmm_int4_ref(x.reshape(-1, 896), ql.codes, ql.scales)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 128),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("call", ["int8", "int4", "quantize"])
def test_tpu_backend_raises_instead_of_reference(monkeypatch, call):
    """On a TPU backend a shape the kernels cannot tile raises; the jnp
    reference is an off-TPU test fallback only."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 100))
    x = jnp.ones((4, 256))
    with pytest.raises(ValueError, match="no Pallas tiling"):
        if call == "int8":
            codes, scales = ref.group_quantize_ref(w, 128)
            ops.quantized_matmul(x, codes, scales)
        elif call == "int4":
            codes, scales = ref.group_quantize_ref(w, 128, bits=4)
            ops.quantized_matmul_int4(x, ref.pack_int4_ref(codes), scales)
        else:
            ops.group_quantize(w, group_size=128)


def test_use_interpret_follows_backend(monkeypatch):
    """Interpret mode is exactly 'no TPU attached': on a TPU backend the
    kernels always compile."""
    from repro.kernels import pallas_env
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_env.use_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert pallas_env.use_interpret() is True
