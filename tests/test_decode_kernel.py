"""The fused write-and-attend decode kernel vs its shared reference.

``quantized_decode_attention`` (kernels/decode_attn.py) is the decode
step's attention primitive: for one layer of the engine's head-major
int8 cache stack ([L, B, KV, dh, T], the layer picked by index) it
writes each row's new entry in place and reads the codes + per-vector
scales straight from the stack, dequantizing per-tile in VMEM.  The
house bitwise-parity invariant extends down to it:
``quantized_decode_attention_ref`` — the plain-Python oracle built on
the SAME per-tile update — must match the kernel bit for bit, outputs
and written stack alike, across stored bit-widths, head shapes, cache
buckets, tile widths, sliding windows and layers of the stack; and
cache-bucket padding must be invisible to the outputs.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn import (cache_layout,
                                       quantized_decode_attention,
                                       quantized_decode_attention_ref)
from repro.kernels.quantize import kv_quantize
from repro.kernels.ref import decode_attention_ref


def _quantize(x, b_kv):
    """Float [..., dh] -> (codes, scales), raw with ones for b_kv >= 16."""
    if b_kv >= 16:
        return x, jnp.ones(x.shape[:-1], jnp.float32)
    return kv_quantize(x, b_kv)


def _case(b, h, kv, dh, t, b_kv, seed=0, n_layers=1):
    """Random [B, 1, H, dh] query, a quantized n_layers-deep cache stack
    with ragged per-row lengths (every row shorter than the bucket),
    and one new quantized entry per row."""
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q = normal(b, 1, h, dh)
    kc, ks = cache_layout(*_quantize(normal(n_layers, b, t, kv, dh), b_kv))
    vc, vs = cache_layout(*_quantize(normal(n_layers, b, t, kv, dh), b_kv))
    lens = jnp.asarray(rng.integers(1, t + 1, size=b), jnp.int32)
    (kn, ksn), (vn, vsn) = (_quantize(normal(b, kv, dh), b_kv)
                            for _ in range(2))
    return q, (kc, vc, ks, vs), lens, (kn, vn, ksn, vsn)


def _same(a, b):
    """Bitwise equality of two arrays, or of two tuples of arrays."""
    if isinstance(a, tuple):
        return len(a) == len(b) and all(map(_same, a, b))
    return np.array_equal(np.asarray(a), np.asarray(b))


def _run(fn, case, layer=0, **kw):
    q, stack, lens, new = case
    return fn(q, *stack, lens, layer, new, **kw)


# the ladder the engine actually serves: every stored bit-width times a
# head-dim / cache-bucket grid covering single- and multi-tile grids
LADDER = [(dh, t, bt)
          for dh in (8, 16, 32)
          for (t, bt) in ((16, 16), (64, 16), (128, 32))]


@pytest.mark.parametrize("b_kv", [4, 8, 16])
@pytest.mark.parametrize("dh,t,bt", LADDER)
def test_kernel_matches_reference_bitwise(b_kv, dh, t, bt):
    case = _case(2, 4, 2, dh, t, b_kv, seed=dh * 1000 + t + b_kv)
    out, stack = _run(quantized_decode_attention, case, block_t=bt)
    want, want_stack = _run(quantized_decode_attention_ref, case,
                            block_t=bt)
    assert _same(out, want), (
        f"b_kv={b_kv} dh={dh} t={t} bt={bt}: kernel diverged from the "
        "shared reference")
    assert _same(stack, want_stack)


@pytest.mark.parametrize("b_kv", [4, 8])
@pytest.mark.parametrize("window", [3, 7])
def test_kernel_matches_reference_sliding_window(b_kv, window):
    case = _case(2, 4, 2, 16, 64, b_kv, seed=window)
    out, _ = _run(quantized_decode_attention, case, window=window,
                  block_t=16)
    want, _ = _run(quantized_decode_attention_ref, case, window=window,
                   block_t=16)
    assert _same(out, want)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_kernel_reads_one_layer_of_the_stack(layer):
    """The layer index picks its layer out of the stack in place: the
    output over layer ``layer`` of a 3-deep stack is bitwise the output
    over a one-layer stack holding just that layer's slice, and matches
    the reference on that slice; the other layers come back untouched."""
    q, stack, lens, new = _case(2, 4, 2, 16, 64, 8, seed=11, n_layers=3)
    out, written = quantized_decode_attention(q, *stack, lens, layer, new,
                                              block_t=16)
    one = tuple(c[layer:layer + 1] for c in stack)
    alone, alone_written = quantized_decode_attention(q, *one, lens, 0,
                                                      new, block_t=16)
    want, _ = quantized_decode_attention_ref(q, *one, lens, 0, new,
                                             block_t=16)
    assert _same(out, alone)
    assert _same(out, want)
    for got, sole, before in zip(written, alone_written, stack):
        assert _same(got[layer], sole[0])
        assert _same(np.delete(np.asarray(got), layer, axis=0),
                     np.delete(np.asarray(before), layer, axis=0))
    other, _ = quantized_decode_attention(q, *stack, lens,
                                          (layer + 1) % 3, new, block_t=16)
    assert not _same(out, other)


@pytest.mark.parametrize("b_kv", [4, 8, 16])
@pytest.mark.parametrize("t,bt", [(16, 16), (64, 16)])
def test_kernel_writes_entries_in_place(b_kv, t, bt):
    """Each row's entry lands at position len - 1 of the layer and
    nowhere else, and the step attends over it: the output is the
    attention over a cache that already held the entries."""
    b, layer = 3, 1
    q, stack, lens, new = _case(b, 4, 2, 16, t, b_kv, seed=t + b_kv,
                                n_layers=3)
    out, written = quantized_decode_attention(q, *stack, lens, layer, new,
                                              block_t=bt)
    pos = np.asarray(lens) - 1
    held = []
    for before, after, e in zip(stack, written, new):
        want = np.array(before)
        e = np.asarray(e).reshape(want.shape[1:4])
        for r in range(b):
            want[layer, r, :, :, pos[r]] = e[r]
        assert np.array_equal(want, np.asarray(after))
        held.append(jnp.asarray(want))
    # writing what the cache already holds changes nothing
    again, rewritten = quantized_decode_attention(q, *held, lens, layer,
                                                  new, block_t=bt)
    assert _same(out, again)
    assert _same(rewritten, tuple(held))


def test_kernel_write_clamps_past_the_bucket():
    """A dead slot's length keeps counting past the bucket: its entry
    lands on the last position, as ``dynamic_update_slice`` clamps, and
    no other row or layer is touched."""
    t = 32
    q, stack, _, new = _case(2, 4, 2, 16, t, 8, seed=5, n_layers=2)
    lens = jnp.asarray([t + 9, 5], jnp.int32)
    out, written = quantized_decode_attention(q, *stack, lens, 0, new,
                                              block_t=16)
    want, want_written = quantized_decode_attention_ref(
        q, *stack, lens, 0, new, block_t=16)
    assert _same(out, want)
    assert _same(written, want_written)
    got = np.asarray(written[0])
    np.testing.assert_array_equal(got[0, 0, :, :, t - 1],
                                  np.asarray(new[0][0]))
    np.testing.assert_array_equal(got[0, 1, :, :, 4], np.asarray(new[0][1]))
    np.testing.assert_array_equal(got[1], np.asarray(stack[0][1]))


def test_gqa_head_fold():
    """H query heads sharing KV groups: folding [B, 1, H, dh] into
    (B*KV, G, dh) kernel rows must keep each group's queries attending
    its own KV stream — checked against the whole-cache oracle of
    kernels/ref.py, on a middle layer of the stack."""
    q, stack, lens, new = _case(2, 8, 2, 16, 32, 8, seed=3, n_layers=3)
    out, written = quantized_decode_attention(q, *stack, lens, 1, new,
                                              block_t=16)
    want = decode_attention_ref(q, *written, lens, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("grow", [16, 96])
def test_cache_bucket_padding_is_attention_invisible(grow):
    """Growing the cache bucket around identical live entries must not
    change the output by a single bit: padded tiles are fully masked,
    and a fully-masked tile's online-softmax update is an exact no-op
    (the hypothesis-driven version lives in test_properties.py)."""
    q, stack, lens, new = _case(2, 4, 2, 16, 32, 8, seed=grow)
    pad = [(0, 0)] * 4 + [(0, grow)]
    out, _ = quantized_decode_attention(q, *stack, lens, 0, new,
                                        block_t=16)
    out_pad, _ = quantized_decode_attention(
        q, *(jnp.pad(c, pad) for c in stack), lens, 0, new, block_t=16)
    assert _same(out, out_pad)


def test_raw_16bit_container_is_exact():
    """b_kv >= 16 stores the raw cache with ones scales through the same
    kernel: dequantization is then x * 1.0, so the quantized path must
    equal unquantized flash-decoding exactly."""
    q, (k, v, ones, _), lens, new = _case(2, 4, 2, 16, 32, 16, seed=9)
    out, _ = quantized_decode_attention(q, k, v, ones, ones, lens, 0, new,
                                        block_t=16)
    want, _ = quantized_decode_attention(q, k * 1.0, v * 1.0, ones, ones,
                                         lens, 0, new, block_t=16)
    assert _same(out, want)
    assert np.isfinite(np.asarray(out)).all()
