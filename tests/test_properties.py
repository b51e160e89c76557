"""Property-based invariants for the serving-shape ladders
(``kernels.bucketing``), the quantization pack/unpack round-trips
(``kernels.quantize`` / ``kernels.ref`` / ``core.quantization``) — the
two pieces of pure arithmetic the decode engine's compile-count bound
and KV-cache parity rest on (DESIGN.md §10, §12) — and the codesign
solvers' contract with the cost model: a feasible solution must
actually meet its budgets under independent re-evaluation, and
loosening budgets must never worsen the bound (DESIGN.md §12, §16).

Runs under hypothesis when installed; otherwise the ``@given`` tests
skip (see ``_hypothesis_compat``) and the deterministic spot checks
below still run everywhere.
"""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st  # soft dep: skips property tests when absent

from repro.core import codesign, cost_model
from repro.core.cost_model import SystemParams
from repro.core.quantization import (pack_int4, unpack_int4, wire_bytes)
from repro.kernels import ref
from repro.kernels.bucketing import (DEFAULT_SEQ_BASE, next_geometric,
                                     row_bucket, seq_bucket, seq_ladder)
from repro.kernels.quantize import (kv_cache_bytes, kv_dequantize,
                                    kv_levels, kv_quantize)

# ---------------------------------------------------------------------------
# bucket-ladder invariants (DESIGN.md §10)
# ---------------------------------------------------------------------------


@settings(max_examples=100, deadline=None)
@given(s=st.integers(min_value=1, max_value=100_000))
def test_seq_bucket_covers_and_is_idempotent(s):
    b = seq_bucket(s)
    assert b >= s                       # padding never truncates
    assert seq_bucket(b) == b           # snapping is idempotent
    # tight: the next rung down would not cover s (or s is below base)
    assert b == DEFAULT_SEQ_BASE or b // 2 < s


@settings(max_examples=100, deadline=None)
@given(a=st.integers(min_value=1, max_value=100_000),
       b=st.integers(min_value=1, max_value=100_000))
def test_seq_bucket_monotone(a, b):
    lo, hi = min(a, b), max(a, b)
    assert seq_bucket(lo) <= seq_bucket(hi)


@settings(max_examples=100, deadline=None)
@given(max_s=st.integers(min_value=1, max_value=100_000))
def test_seq_ladder_geometric_and_covering(max_s):
    ladder = seq_ladder(max_s)
    assert ladder[0] == DEFAULT_SEQ_BASE
    assert ladder[-1] == seq_bucket(max_s) >= max_s
    for lo, hi in zip(ladder, ladder[1:]):
        assert hi == 2 * lo             # strictly geometric, no gaps
    # every length <= max_s snaps to a rung of this ladder: warmup over
    # the ladder precompiles everything traffic can dispatch
    assert all(seq_bucket(s) in ladder
               for s in (1, max_s // 2 or 1, max_s))


@settings(max_examples=100, deadline=None)
@given(max_a=st.integers(min_value=1, max_value=10_000),
       max_b=st.integers(min_value=1, max_value=10_000))
def test_seq_ladder_prefix_stable(max_a, max_b):
    """A longer horizon only appends rungs — it never reshuffles the
    existing ones, so growing ``warmup()`` coverage never invalidates
    already-compiled variants."""
    lo, hi = sorted((max_a, max_b))
    a, b = seq_ladder(lo), seq_ladder(hi)
    assert b[:len(a)] == a


@settings(max_examples=100, deadline=None)
@given(n=st.integers(min_value=1, max_value=1_000_000),
       base=st.integers(min_value=1, max_value=512),
       ratio=st.integers(min_value=2, max_value=5))
def test_next_geometric_minimal(n, base, ratio):
    g = next_geometric(n, base, ratio)
    assert g >= n and g >= base
    assert g == base or g // ratio < n  # the next rung down is too small


@settings(max_examples=100, deadline=None)
@given(m=st.integers(min_value=1, max_value=100_000))
def test_row_bucket_mxu_aligned(m):
    b = row_bucket(m)
    assert b >= m and b % 128 == 0
    assert b == 128 or b // 2 < m


def test_bucket_spot_checks():
    # deterministic floor so the invariants are exercised even without
    # hypothesis installed
    assert seq_bucket(1) == 16 and seq_bucket(17) == 32
    assert seq_ladder(48) == (16, 32, 64)
    assert row_bucket(129) == 256
    with pytest.raises(ValueError):
        seq_bucket(0)


# ---------------------------------------------------------------------------
# pack/unpack round-trips
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(rows=st.integers(min_value=1, max_value=8),
       cols=st.integers(min_value=1, max_value=16),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_pack_int4_round_trip(rows, cols, seed):
    rng = np.random.default_rng(seed)
    codes = rng.integers(-8, 8, size=(rows, 2 * cols)).astype(np.int8)
    out = np.asarray(unpack_int4(pack_int4(codes)))
    np.testing.assert_array_equal(out, codes)
    assert wire_bytes(codes.size, 4) == codes.size // 2


@settings(max_examples=50, deadline=None)
@given(k2=st.integers(min_value=1, max_value=16),
       n=st.integers(min_value=1, max_value=16),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_pack_int4_ref_round_trip(k2, n, seed):
    rng = np.random.default_rng(seed)
    codes = rng.integers(-8, 8, size=(2 * k2, n)).astype(np.int8)
    out = np.asarray(ref.unpack_int4_ref(ref.pack_int4_ref(codes)))
    np.testing.assert_array_equal(out, codes)


def test_pack_int4_rejects_odd_axis():
    with pytest.raises(ValueError):
        pack_int4(np.zeros((3, 5), np.int8))


# ---------------------------------------------------------------------------
# KV-cache quantization (DESIGN.md §12)
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(bits=st.sampled_from([2, 4, 8]),
       t=st.integers(min_value=1, max_value=6),
       d=st.integers(min_value=1, max_value=8),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_kv_quantize_round_trip_bounded(bits, t, d, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((t, d)).astype(np.float32) * 3.0
    codes, scales = kv_quantize(x, bits)
    codes, scales = np.asarray(codes), np.asarray(scales)
    lv = kv_levels(bits)
    assert codes.dtype == np.int8
    assert np.abs(codes).max() <= lv
    assert scales.shape == x.shape[:-1]
    # symmetric uniform quantization: error is at most half a step per
    # element (round-to-nearest), scale = absmax / levels per vector
    dq = np.asarray(kv_dequantize(codes, scales))
    np.testing.assert_allclose(dq, x, atol=float(scales.max()) / 2 + 1e-6)


@settings(max_examples=50, deadline=None)
@given(bits=st.sampled_from([4, 8]),
       d=st.integers(min_value=1, max_value=8))
def test_kv_quantize_zero_vector_is_safe(bits, d):
    x = np.zeros((3, d), np.float32)
    codes, scales = kv_quantize(x, bits)
    assert np.all(np.asarray(codes) == 0)
    assert np.all(np.asarray(scales) == 1.0)    # no divide-by-zero scale
    np.testing.assert_array_equal(np.asarray(kv_dequantize(codes, scales)),
                                  x)


@settings(max_examples=25, deadline=None)
@given(b_kv=st.sampled_from([4, 8, 16]),
       dh=st.sampled_from([8, 16]),
       len0=st.integers(min_value=1, max_value=32),
       len1=st.integers(min_value=1, max_value=32),
       grow=st.sampled_from([16, 32, 96]),
       seed=st.integers(min_value=0, max_value=2**16))
def test_cache_bucket_padding_is_attention_invisible(b_kv, dh, len0, len1,
                                                     grow, seed):
    """Growing a request's cache bucket (T -> T + grow) around identical
    live entries changes the fused decode attention output by ZERO bits:
    padded positions sit in fully-masked tiles, and a fully-masked
    tile's online-softmax update is an exact no-op (DESIGN.md §13).
    This is the invariant that lets the engine bucket each request's
    cache from its own (prompt, budget) independent of its batch-mates
    while staying bitwise-comparable to the sequential reference."""
    import jax.numpy as jnp

    from repro.kernels.decode_attn import (cache_layout,
                                           quantized_decode_attention)

    t = 32
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)

    def quantize(x):
        if b_kv >= 16:
            return x, jnp.ones(x.shape[:-1], jnp.float32)
        return kv_quantize(x, b_kv)

    q = normal(2, 1, 4, dh)
    # the engine's one-layer stack: codes [1, B, KV, dh, T]
    kc, ks = cache_layout(*quantize(normal(1, 2, t, 2, dh)))
    vc, vs = cache_layout(*quantize(normal(1, 2, t, 2, dh)))
    (kn, ksn), (vn, vsn) = (quantize(normal(2, 2, dh)) for _ in range(2))
    lens = jnp.asarray([len0, len1], jnp.int32)
    pad = [(0, 0)] * 4 + [(0, grow)]
    out, _ = quantized_decode_attention(
        q, kc, vc, ks, vs, lens, 0, (kn, vn, ksn, vsn), block_t=16)
    out_pad, _ = quantized_decode_attention(
        q, *(jnp.pad(c, pad) for c in (kc, vc, ks, vs)), lens, 0,
        (kn, vn, ksn, vsn), block_t=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_pad))


# ---------------------------------------------------------------------------
# codesign solver contract (DESIGN.md §12, §16)
# ---------------------------------------------------------------------------

# a decode-serving-shaped operating point: FLOP counts at smoke scale
# with a cache stream sized so b_kv is a live decision (kv_delay(16) =
# 0.5 s against t0 of a few seconds)
_P = SystemParams(n_flop_agent=5.0e8, n_flop_server=7.0e8,
                  kv_bytes_full=2.0e6, kv_bw_bps=4.0e6, kv_power_w=2.0)
_BUDGETS = st.tuples(st.floats(min_value=0.3, max_value=6.0),
                     st.floats(min_value=0.3, max_value=6.0))
_LAM = st.floats(min_value=0.05, max_value=5.0)


@settings(max_examples=60, deadline=None)
@given(lam=_LAM, lam_kv=_LAM, budgets=_BUDGETS)
def test_solve_decode_feasible_meets_budgets(lam, lam_kv, budgets):
    """A feasible solve_decode answer survives independent
    re-evaluation: plugging (b̂, f, f̃, b_kv) back into the cost model
    reproduces the reported delay/energy and respects (T0, E0)."""
    t0, e0 = budgets
    sol = codesign.solve_decode(lam, lam_kv, _P, t0, e0)
    if sol is None:        # infeasible corner: nothing to re-evaluate
        return
    d = float(cost_model.total_delay(sol.b_hat, sol.f, sol.f_server,
                                     _P, b_kv=sol.b_kv))
    e = float(cost_model.total_energy(sol.b_hat, sol.f, sol.f_server,
                                      _P, b_kv=sol.b_kv))
    assert sol.feasible
    assert d == pytest.approx(sol.delay, rel=1e-9)
    assert e == pytest.approx(sol.energy, rel=1e-9)
    assert d <= t0 * (1 + 1e-6) and e <= e0 * (1 + 1e-6)


@settings(max_examples=40, deadline=None)
@given(lam=_LAM, lam_kv=_LAM, budgets=_BUDGETS)
def test_solve_speculative_feasible_meets_budgets(lam, lam_kv, budgets):
    """Same contract for the speculative joint solve: the realized
    per-delivered-token round cost — draft chain, ONE batched verify
    forward, k+1 cache reads, expected rollback, all divided by τ —
    must fit the same per-token (T0, E0)."""
    t0, e0 = budgets
    sol = codesign.solve_speculative(lam, lam_kv, _P, t0, e0)
    if sol is None:
        return
    tau = sol.tokens_per_round
    d = float(cost_model.speculative_round_delay(
        sol.b_hat, sol.f, sol.f_server, sol.b_draft, sol.k, tau, _P,
        b_kv=sol.b_kv)) / tau
    e = float(cost_model.speculative_round_energy(
        sol.b_hat, sol.f, sol.f_server, sol.b_draft, sol.k, tau, _P,
        b_kv=sol.b_kv)) / tau
    assert sol.feasible
    assert d == pytest.approx(sol.delay, rel=1e-9)
    assert e == pytest.approx(sol.energy, rel=1e-9)
    assert d <= t0 * (1 + 1e-6) and e <= e0 * (1 + 1e-6)
    assert 1.0 <= tau <= sol.k + 1
    assert 0.0 <= sol.alpha <= 1.0


@settings(max_examples=40, deadline=None)
@given(lam=_LAM, lam_kv=_LAM, budgets=_BUDGETS,
       slack=st.tuples(st.floats(min_value=0.0, max_value=4.0),
                       st.floats(min_value=0.0, max_value=4.0)))
def test_loosening_budgets_never_increases_decode_bound(lam, lam_kv,
                                                        budgets, slack):
    """More (T0, E0) slack can only help: the feasible set grows, so
    the minimized joint distortion bound is monotone non-increasing."""
    t0, e0 = budgets
    tight = codesign.solve_decode(lam, lam_kv, _P, t0, e0)
    if tight is None:
        return
    loose = codesign.solve_decode(lam, lam_kv, _P, t0 + slack[0],
                                  e0 + slack[1])
    assert loose is not None
    assert loose.objective <= tight.objective + 1e-9


@settings(max_examples=25, deadline=None)
@given(lam=_LAM, lam_kv=_LAM, budgets=_BUDGETS,
       slack=st.tuples(st.floats(min_value=0.0, max_value=4.0),
                       st.floats(min_value=0.0, max_value=4.0)))
def test_loosening_budgets_never_increases_spec_bound(lam, lam_kv,
                                                      budgets, slack):
    t0, e0 = budgets
    tight = codesign.solve_speculative(lam, lam_kv, _P, t0, e0)
    if tight is None:
        return
    loose = codesign.solve_speculative(lam, lam_kv, _P, t0 + slack[0],
                                       e0 + slack[1])
    assert loose is not None
    assert loose.objective <= tight.objective + 1e-9


@settings(max_examples=100, deadline=None)
@given(d1=st.floats(min_value=0.0, max_value=50.0),
       d2=st.floats(min_value=0.0, max_value=50.0),
       gamma=st.floats(min_value=0.1, max_value=10.0))
def test_acceptance_in_unit_interval_and_monotone(d1, d2, gamma):
    """The §16 acceptance estimator is a probability and degrades (never
    improves) as the draft's distortion bound grows."""
    lo, hi = sorted((d1, d2))
    a_lo = codesign.acceptance_from_distortion(lo, gamma)
    a_hi = codesign.acceptance_from_distortion(hi, gamma)
    assert 0.0 <= a_hi <= a_lo <= 1.0


@settings(max_examples=100, deadline=None)
@given(lam=_LAM, gamma=st.floats(min_value=0.1, max_value=10.0))
def test_acceptance_monotone_in_draft_bits(lam, gamma):
    """More draft fidelity never lowers modeled acceptance — the shape
    the benchmark checks against *measured* acceptance."""
    rates = [codesign.acceptance_rate(b, lam, gamma) for b in (2, 4, 8)]
    assert all(0.0 <= r <= 1.0 for r in rates)
    assert rates == sorted(rates)


@settings(max_examples=100, deadline=None)
@given(a1=st.floats(min_value=0.0, max_value=1.0),
       a2=st.floats(min_value=0.0, max_value=1.0),
       k=st.integers(min_value=1, max_value=16))
def test_expected_tokens_per_round_bounds(a1, a2, k):
    """τ(α, k) = Σ_{i=0..k} αⁱ ∈ [1, k+1], monotone in both acceptance
    and lookahead — the engine's billing divides by it, so these bounds
    keep every per-token cost finite and positive."""
    lo, hi = sorted((a1, a2))
    t_lo = codesign.expected_tokens_per_round(lo, k)
    t_hi = codesign.expected_tokens_per_round(hi, k)
    assert 1.0 <= t_lo <= t_hi <= k + 1
    assert t_hi <= codesign.expected_tokens_per_round(hi, k + 1)


def test_codesign_contract_spot_checks():
    """Deterministic floor for the solver-contract properties, exercised
    even without hypothesis installed."""
    sol = codesign.solve_decode(1.0, 1.0, _P, 2.0, 2.0)
    assert sol is not None and sol.feasible
    assert float(cost_model.total_delay(
        sol.b_hat, sol.f, sol.f_server, _P, b_kv=sol.b_kv)) <= 2.0 * (1 + 1e-6)
    spec = codesign.solve_speculative(1.0, 1.0, _P, 2.0, 2.0)
    assert spec is not None and spec.feasible
    # the joint draft variables must pay for themselves: strictly lower
    # distortion bound per expected delivered token
    assert spec.objective < sol.objective
    assert codesign.expected_tokens_per_round(0.0, 4) == 1.0
    assert codesign.expected_tokens_per_round(1.0, 4) == 5.0


def test_kv_quantize_spot_checks():
    assert kv_levels(4) == 7 and kv_levels(8) == 127
    x = np.array([[1.0, -2.0, 0.5, 2.0]], np.float32)
    codes, scales = kv_quantize(x, 8)
    assert float(np.asarray(scales)[0]) == pytest.approx(2.0 / 127)
    np.testing.assert_allclose(np.asarray(kv_dequantize(codes, scales)),
                               x, atol=2.0 / 127 / 2 + 1e-7)
    # container accounting matches the wire format: packed int4, int8,
    # raw float above the ladder
    shape = (2, 3, 4, 5, 8)
    n = int(np.prod(shape))
    n_vec = n // shape[-1]
    assert kv_cache_bytes(shape, 4) == (n + 1) // 2 + 4 * n_vec
    assert kv_cache_bytes(shape, 8) == n + 4 * n_vec
    assert kv_cache_bytes(shape, 16) == 2 * n
