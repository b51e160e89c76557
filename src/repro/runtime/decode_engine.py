"""Continuous-batching decode over a device-resident quantized KV cache
(DESIGN.md §12, §13).

Everything the engines of ``serve_engine.py`` do is one prefill-style
forward per request.  Embodied-agent traffic is token-by-token decode:
a request prefills once, then occupies the accelerator for dozens of
single-token steps whose cost is dominated by streaming the KV cache.
This module adds that serving mode on top of the PR-4 compiled fast
path, with four commitments:

1.  **Continuous batching.**  A request is admitted into a free decode
    slot the moment one exists and retires the moment its budget is
    spent — there is no batch barrier.  The FIFO-barrier policy (admit a
    full batch, run it to completion, only then refill) is kept as
    ``admission="barrier"`` on the same engine, so the benchmark's
    throughput comparison is policy-for-policy on identical code.

2.  **Quantized KV cache, attended directly.**  Cache entries are stored
    as int8-held codes plus one f32 scale per head vector
    (``kernels.quantize.kv_quantize`` — the weight quantizers' exact
    scale/round/clip rule) at a stored bit-width ``b_kv`` from the
    realizable container ladder.  The decode step never materializes a
    dequantized copy: ``DecoderLM.decode_step_q`` quantizes the fresh
    entry *before* writing it and attends through
    ``kernels.decode_attn.quantized_decode_attention``, which
    dequantizes per-tile in VMEM.  ``b_kv`` stays the third codesign
    variable (``codesign.solve_decode`` /
    ``mixed_precision.allocate_bits_decode``).

3.  **Device residency (DESIGN.md §13).**  Each slot block's
    ``k_codes/v_codes/k_scales/v_scales/pos/tok`` live as on-device
    arrays that persist across engine steps and are *donated* to each
    executable (XLA updates them in place).  The host syncs only at the
    real serving boundaries: prompt tokens in at admission, generated
    token blocks out for streaming/retirement.  ``DecodeReport`` counts
    the actual h2d/d2h bytes so the benchmark can show the per-token
    transfer volume collapsing.

4.  **Bitwise parity.**  Greedy decode through the batched engine equals
    the non-batched sequential reference token-for-token.  The load-
    bearing invariants: each request's cache length is bucketed from its
    *own* parameters (``T = seq_bucket(prompt_len + max_new_tokens)``,
    never a batch max); every per-row op in the decode graph is
    row-independent, so batch width B does not change row values (the
    §7 house invariant); and multi-token stepping is fused through a
    ``lax.while_loop`` whose trip count is a *runtime* argument — the
    §10 isolation trick, so each token step compiles to one fixed XLA
    sub-computation and any chunking of the same step sequence (engine
    chunks vs reference chunks vs an elastic split/resume) produces
    identical bits.  Engine and reference share the same traced
    functions at different batch widths.

Executables are AOT-compiled (``fastpath.aot_compile``) and memoized in
a :class:`~repro.runtime.fastpath.CompiledForwardCache`: prefill+scatter
is keyed on (prompt bucket, cache bucket, batch, b_kv), the fused decode
chunk on (batch, cache bucket, b_kv), so the post-warmup compile count
is bounded by the (prompt, cache)-bucket pairs plus cache rungs, times
the distinct cache bit-widths.

Costs are virtual-clock, billed at the *padded* workload exactly as
before: each token step inside a fused chunk bills all ``max_batch``
slots plus the full cache read at ``b_kv``.  A chunk never overruns a
scheduling boundary — its step count is clamped to the tightest of the
live slots' remaining budgets, the next queued arrival, and the EOS
early-exit inside the executable — so admission and retirement timing
on the virtual clock are identical to stepping one token at a time.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mixed_precision as mp
from repro.core.cost_model import (SystemParams, agent_delay, agent_energy,
                                   kv_delay, kv_energy, server_delay,
                                   server_energy)
from repro.core.quantization import QuantConfig, QuantPlan
from repro.core.rate_distortion import exponential_mle
from repro.kernels.bucketing import DEFAULT_SEQ_BASE, seq_bucket, seq_ladder
from repro.kernels.decode_attn import cache_layout
from repro.kernels.quantize import kv_cache_bytes, kv_quantize
from repro.obs import NULL_METRICS, NULL_TRACER, ReportBase

from .fastpath import CompiledForwardCache, _sds, aot_compile
from .qat import fake_quantize_agent
from .serve_engine import CodesignCache, QosClass, fit_lambda

__all__ = [
    "DecodeRequest",
    "DecodeResponse",
    "ClassDecodeStats",
    "DecodeReport",
    "DecodeEngine",
    "fit_kv_lambda",
    "greedy_decode_reference",
]

# the fused decode executable's fixed output-block width: one compiled
# chunk emits up to this many tokens per slot.  A constant (never a
# compile key) so chunk size costs no extra executables and — by the
# while-loop isolation argument — no bitwise risk: a chunk of k steps is
# the same k loop iterations regardless of where the host cuts them.
_CHUNK = 64

# the KV-cache layout this engine manages slots in; models exposing the
# decode hooks over a different state shape (conv streams, recurrent
# cells, cross-attention caches) cannot be sloted into it
_DECODE_CACHE_AXES = {
    "k": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
    "v": ("layers", "batch", "cache_seq", "kv_heads", "head_dim"),
    "len": ("batch",),
}


def decode_protocol_gap(model) -> Optional[str]:
    """Why ``model`` cannot be decode-served (None when it can).

    Requires the full DecoderLM decode protocol — ``prefill`` /
    ``init_cache`` / ``decode_step`` / ``decode_step_q`` — *and* the
    [L, B, T, KV, dh] KV cache a prefill returns, which the engine turns
    into its head-major slot arrays.
    Hybrid/xLSTM/enc-dec families expose same-named hooks over different
    state shapes; they are rejected here, not by a shape error three
    calls in.
    """
    missing = [h for h in ("prefill", "init_cache", "decode_step",
                           "decode_step_q", "cache_axes")
               if not hasattr(model, h)]
    if missing:
        return f"lacks the {'/'.join(missing)} decode hook(s)"
    axes = model.cache_axes()
    if axes != _DECODE_CACHE_AXES:
        return ("decode state is not the [layers, batch, cache_seq, "
                "kv_heads, head_dim] KV cache")
    return None


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecodeRequest:
    """One queued decode request: a prompt plus a generation budget."""
    request_id: int
    tokens: np.ndarray          # int32 [P] prompt
    qos: str
    max_new_tokens: int
    arrival_s: float            # virtual arrival time
    submitted_wall_s: float = 0.0   # host clock at submit(), metrics on


@dataclasses.dataclass(frozen=True)
class DecodeResponse:
    """A retired request: greedy continuation + its latency accounting."""
    request_id: int
    qos: str
    tokens: np.ndarray          # int32, generated greedily (<= max_new)
    prompt_len: int
    b_kv: int                   # stored cache bit-width it decoded under
    ttft_s: float               # arrival -> first token (virtual clock)
    itl_mean_s: float           # mean inter-token latency (0 if 1 token)
    finished_s: float
    cancelled: bool = False     # retired mid-decode by cancel()


@dataclasses.dataclass(frozen=True)
class ClassDecodeStats(ReportBase):
    """Per-QoS-class latency aggregates of a :class:`DecodeReport`."""
    qos: str
    b_hat: int
    b_kv: int
    requests: int
    tokens: int
    ttft_mean_s: float
    ttft_max_s: float
    itl_mean_s: float
    plan_bits: tuple = ()       # per-agent-layer bits under a mixed plan
    itl_p50_s: float = 0.0      # inter-token latency percentiles
    itl_p95_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class DecodeReport(ReportBase):
    """Whole-run aggregates of a :class:`DecodeEngine` (the decode
    counterpart of ``serve_engine.EngineReport``, streamed per class)."""
    requests_served: int
    cancelled: int
    tokens_generated: int
    prefills: int
    decode_rounds: int
    total_delay_s: float        # virtual clock at the end of the run
    total_energy_j: float
    throughput_tps: float       # generated tokens / modeled second
    throughput_rps: float
    admission: str              # "continuous" | "barrier"
    classes: tuple = ()         # ClassDecodeStats per QoS class
    kv_bytes: int = 0           # stored cache bytes across admissions
    kv_bytes_full: int = 0      # same cache at full precision
    codesign_hits: int = 0      # this engine's cache attribution
    codesign_misses: int = 0
    compile_hits: int = 0
    compile_misses: int = 0
    compiled_variants: int = 0
    h2d_bytes: int = 0          # measured host->device traffic (§13)
    d2h_bytes: int = 0          # measured device->host traffic


# ---------------------------------------------------------------------------
# cache-activation statistic
# ---------------------------------------------------------------------------

_KV_LAMBDA_MEMO: Dict[tuple, float] = {}


def _params_fingerprint(params) -> tuple:
    """A cheap hashable identity for a parameter tree: every leaf's
    (shape, dtype) plus the first leaf's head bytes.  Distinguishes
    differently-initialized trees of the same architecture without
    hashing gigabytes; collisions would need identical leading weights
    on identical structures."""
    leaves = jax.tree_util.tree_leaves(params)
    head = np.asarray(leaves[0]).reshape(-1)[:8].tobytes()
    return (tuple((tuple(lf.shape), str(lf.dtype)) for lf in leaves), head)


def fit_kv_lambda(model, params, *, seq: int = 16) -> float:
    """MLE λ_kv over K/V cache magnitudes from one calibration prefill.

    The decode codesign needs a rate parameter for the *cached
    activations*, symmetric with ``fit_lambda``'s weight statistic.  One
    deterministic prompt (``arange % vocab``) at full precision is
    calibration enough at the fidelity of the exponential model — and
    determinism keeps the codesign cache key stable across runs.

    Memoized per (arch config, seq, parameter fingerprint): the prefill
    is a real forward pass, and every :class:`DecodeEngine` construction
    over the same model/params would otherwise re-run it.
    """
    key = (model.cfg, int(seq), _params_fingerprint(params))
    if key not in _KV_LAMBDA_MEMO:
        cfg = model.cfg
        toks = (np.arange(seq, dtype=np.int64)
                % int(cfg.vocab_size)).astype(np.int32)[None]
        _, cache = model.prefill(params, {"tokens": jnp.asarray(toks)})
        mags = jnp.concatenate([jnp.abs(cache["k"]).reshape(-1),
                                jnp.abs(cache["v"]).reshape(-1)])
        _KV_LAMBDA_MEMO[key] = float(exponential_mle(mags))
    return _KV_LAMBDA_MEMO[key]


# ---------------------------------------------------------------------------
# traced decode functions (shared by the engine and the reference)
# ---------------------------------------------------------------------------

def _build_prefill(model, b_kv: int) -> Callable:
    """Fused prefill + quantize + slot scatter (DESIGN.md §13).

    (weights, tokens [1, S], last_idx [1], slot [], k_codes, v_codes,
    k_scales, v_scales, pos [B], tok [B]) -> (first greedy token [1],
    updated buffers).  The prompt's cache block is quantized, turned
    head-major (only the prompt's own ``[L, 1, S, KV, dh]`` block is
    transposed, never the slot buffers) and written into decode slot
    ``slot`` of the group's device-resident buffers inside one
    executable — the quantization arithmetic is in-trace, so engine and
    reference share it exactly, and the cache block never visits the
    host.  Buffer positions past the prompt keep the previous
    occupant's stale entries: attention masks positions >= the row's
    cache length, so they are never read before this occupant overwrites
    them token by token.
    """
    raw = b_kv >= 16

    def decode_prefill(weights, tokens, last_idx, slot, kc, vc, ks, vs,
                       pos, tok):
        logits, cache = model.prefill(weights, {"tokens": tokens},
                                      last_index=last_idx)
        tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k, v = cache["k"], cache["v"]       # [L, 1, S, KV, dh]
        if raw:
            kq, vq = k.astype(kc.dtype), v.astype(vc.dtype)
            ksn = jnp.ones(k.shape[:-1], jnp.float32)
            vsn = jnp.ones(v.shape[:-1], jnp.float32)
        else:
            kq, ksn = kv_quantize(k, b_kv)
            vq, vsn = kv_quantize(v, b_kv)
            kq, vq = kq.astype(kc.dtype), vq.astype(vc.dtype)
        kq, ksn = cache_layout(kq, ksn)
        vq, vsn = cache_layout(vq, vsn)
        at5 = (0, slot, 0, 0, 0)
        kc = jax.lax.dynamic_update_slice(kc, kq, at5)
        vc = jax.lax.dynamic_update_slice(vc, vq, at5)
        ks = jax.lax.dynamic_update_slice(ks, ksn, at5)
        vs = jax.lax.dynamic_update_slice(vs, vsn, at5)
        pos = jax.lax.dynamic_update_slice(pos, last_idx + 1, (slot,))
        tok = jax.lax.dynamic_update_slice(tok, tok0, (slot,))
        return tok0, kc, vc, ks, vs, pos, tok

    return decode_prefill


def _build_fused_decode(model, b_kv: int) -> Callable:
    """Multi-token decode chunk as ONE executable (DESIGN.md §13).

    (weights, k_codes, v_codes, k_scales, v_scales, tok [B], pos [B],
    live [B] i32, eos [], n_steps []) -> (token block [B, _CHUNK] i32,
    steps done [], updated buffers).  A ``lax.while_loop`` whose trip
    count ``n_steps`` is a *runtime* argument steps
    ``DecoderLM.decode_step_q`` up to ``n_steps`` times, exiting early
    once every live slot has emitted ``eos`` (pass eos = -1 to disable —
    greedy tokens are always >= 0).  The §10 isolation argument makes
    each iteration one fixed XLA sub-computation, so chunk boundaries
    cannot change bits; dead slots (live = 0) still compute, but every
    op is row-independent so their garbage never escapes the row.  The
    cache buffers ride the loop carry whole: each step writes its new
    entries in place and the kernel reads them where they lie, so no
    step moves a cache-sized array (``tests/test_decode_inplace.py``).
    """

    def decode_chunk(weights, kc, vc, ks, vs, tok, pos, live, eos,
                     n_steps):
        b = tok.shape[0]
        live_m = live > 0
        n = jnp.asarray(n_steps, jnp.int32)

        def cond(carry):
            i = carry[0]
            eos_hit = carry[7]
            return (i < n) & jnp.any(live_m & ~eos_hit)

        def body(carry):
            i, tok, pos, kc, vc, ks, vs, eos_hit, out = carry
            logits, qc = model.decode_step_q(
                weights,
                {"k_codes": kc, "v_codes": vc, "k_scales": ks,
                 "v_scales": vs, "len": pos},
                {"token": tok[:, None], "pos": pos}, b_kv=b_kv)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
            eos_hit = eos_hit | (nxt == eos)
            return (i + 1, nxt, qc["len"], qc["k_codes"], qc["v_codes"],
                    qc["k_scales"], qc["v_scales"], eos_hit, out)

        carry = (jnp.int32(0), tok, pos, kc, vc, ks, vs,
                 jnp.zeros((b,), bool), jnp.zeros((b, _CHUNK), jnp.int32))
        i, tok, pos, kc, vc, ks, vs, _, out = jax.lax.while_loop(
            cond, body, carry)
        return out, i, kc, vc, ks, vs, tok, pos

    return decode_chunk


# the speculative executables' fixed draft-column width
# (``runtime/speculative.py``): lookahead k is a *runtime* argument up
# to this many columns, never a compile key, so sweeping k costs no
# extra executables — the same isolation trick as ``_CHUNK``.
_SPEC_MAX_K = 16


def _build_spec_draft(model, b_kv: int) -> Callable:
    """``k`` greedy draft steps under the DRAFT weight tree
    (DESIGN.md §16).

    (draft_weights, k_codes, v_codes, k_scales, v_scales, tok [B],
    pos [B], n_draft []) -> drafts [B, _SPEC_MAX_K] i32.  The chain
    steps ``decode_step_q`` ``n_draft`` times from the canonical cache
    state, carrying the cache *functionally* in the while-loop and
    discarding it at the end: draft writes are speculative scratch that
    must never reach the canonical slot buffers, so the buffers are NOT
    donated here — rollback is realized as commit-on-verify (only the
    verify executable writes the canonical cache), not as truncation
    after the fact.
    """

    def spec_draft(weights, kc, vc, ks, vs, tok, pos, n_draft):
        b = tok.shape[0]
        n = jnp.asarray(n_draft, jnp.int32)

        def cond(carry):
            return carry[0] < n

        def body(carry):
            i, tok, pos, kc, vc, ks, vs, out = carry
            logits, qc = model.decode_step_q(
                weights,
                {"k_codes": kc, "v_codes": vc, "k_scales": ks,
                 "v_scales": vs, "len": pos},
                {"token": tok[:, None], "pos": pos}, b_kv=b_kv)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
            return (i + 1, nxt, qc["len"], qc["k_codes"], qc["v_codes"],
                    qc["k_scales"], qc["v_scales"], out)

        carry = (jnp.int32(0), tok, pos, kc, vc, ks, vs,
                 jnp.zeros((b, _SPEC_MAX_K), jnp.int32))
        return jax.lax.while_loop(cond, body, carry)[-1]

    return spec_draft


def _build_spec_verify(model, b_kv: int) -> Callable:
    """Verify a round of drafts with the TARGET weights, longest-
    accepted-prefix semantics (DESIGN.md §16).

    (weights, k_codes, v_codes, k_scales, v_scales, tok [B], pos [B],
    live [B] i32, drafts [B, _SPEC_MAX_K] i32, n_draft [], rem [B] i32,
    eos []) -> (token block [B, _SPEC_MAX_K + 1] i32, emitted [B] i32,
    accepted [B] i32, updated buffers).

    Iteration ``i`` feeds each still-active row's current token at its
    position through ``decode_step_q`` — *exactly* the sequential
    reference's next step, so every cache entry an active row writes is
    the entry the reference writes, and every emitted token ``g`` is
    the reference's token.  A row goes inactive after emitting when its
    ``g`` diverges from ``drafts[:, i]`` (``g`` is the correction and
    is already committed), when ``i == n_draft`` (the bonus token), at
    ``eos``, or when its generation budget ``rem`` is spent.  Inactive
    rows are frozen: cache writes are reverted row-wise, ``pos``/``tok``
    held, so a round never commits anything the reference would not —
    delivered tokens per row per round = accepted prefix + 1, bitwise
    the reference stream (the house invariant, extended).
    """

    def spec_verify(weights, kc, vc, ks, vs, tok, pos, live, drafts,
                    n_draft, rem, eos):
        b = tok.shape[0]
        n = jnp.asarray(n_draft, jnp.int32)

        def cond(carry):
            return (carry[0] <= n) & jnp.any(carry[1])

        def body(carry):
            i, act, tok, pos, kc, vc, ks, vs, cnt, acc, out = carry
            logits, qc = model.decode_step_q(
                weights,
                {"k_codes": kc, "v_codes": vc, "k_scales": ks,
                 "v_scales": vs, "len": pos},
                {"token": tok[:, None], "pos": pos}, b_kv=b_kv)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            m5 = act[None, :, None, None, None]
            kc = jnp.where(m5, qc["k_codes"], kc)
            vc = jnp.where(m5, qc["v_codes"], vc)
            ks = jnp.where(m5, qc["k_scales"], ks)
            vs = jnp.where(m5, qc["v_scales"], vs)
            pos = jnp.where(act, qc["len"], pos)
            tok = jnp.where(act, g, tok)
            # all active rows share emission column i (== their cnt);
            # inactive rows' stale columns are never read by the host
            out = jax.lax.dynamic_update_slice(out, g[:, None], (0, i))
            cnt = cnt + act.astype(jnp.int32)
            draft_i = jax.lax.dynamic_index_in_dim(drafts, i, axis=1,
                                                   keepdims=False)
            match = (i < n) & (g == draft_i)
            acc = acc + (act & match).astype(jnp.int32)
            act = act & match & (g != eos) & (cnt < rem)
            return (i + 1, act, tok, pos, kc, vc, ks, vs, cnt, acc, out)

        carry = (jnp.int32(0), live > 0, tok, pos, kc, vc, ks, vs,
                 jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
                 jnp.zeros((b, _SPEC_MAX_K + 1), jnp.int32))
        (_, _, tok, pos, kc, vc, ks, vs, cnt, acc, out) = \
            jax.lax.while_loop(cond, body, carry)
        return out, cnt, acc, kc, vc, ks, vs, tok, pos

    return spec_verify


def _build_spec_round(model, b_kv: int) -> Callable:
    """One full speculative round — draft chain + verify chain — in a
    single executable (DESIGN.md §16).

    (draft_weights, weights, k_codes, v_codes, k_scales, v_scales,
    tok [B], pos [B], live [B] i32, n_draft [], rem [B] i32, eos []) ->
    ``_build_spec_verify``'s outputs.  Semantically this is exactly
    ``_build_spec_draft`` piped into ``_build_spec_verify`` — the draft
    chain still carries the cache functionally and discards it, the
    verify chain still commits only reference tokens — but fused into
    one dispatch: a speculative round is launch-overhead bound (two
    short chains per round), and measured wall throughput is what the
    ``benchmarks/speculative.py`` gate holds against fused decode.  The
    standalone builders above stay as the unit-testable pieces (the
    rejection-position tests drive ``_build_spec_verify`` with crafted
    draft blocks no honest draft chain would produce).
    """
    draft_fn = _build_spec_draft(model, b_kv)
    verify_fn = _build_spec_verify(model, b_kv)

    def spec_round(draft_weights, weights, kc, vc, ks, vs, tok, pos, live,
                   n_draft, rem, eos):
        drafts = draft_fn(draft_weights, kc, vc, ks, vs, tok, pos,
                          n_draft)
        return verify_fn(weights, kc, vc, ks, vs, tok, pos, live,
                         drafts, n_draft, rem, eos)

    return spec_round


def _compile_spec_round(model, params, b_kv: int, batch: int,
                        t_bucket: int):
    codes, scales, vec = _cache_sds(model.cfg, b_kv, batch, t_bucket)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return aot_compile(
        _build_spec_round(model, b_kv),
        (_sds(params), _sds(params), codes, codes, scales, scales, vec,
         vec, vec, scalar, vec, scalar),
        donate_argnums=(2, 3, 4, 5, 6, 7))


def _compile_spec_draft(model, params, b_kv: int, batch: int,
                        t_bucket: int):
    codes, scales, vec = _cache_sds(model.cfg, b_kv, batch, t_bucket)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    # no donation: the canonical cache buffers must survive for verify
    return aot_compile(
        _build_spec_draft(model, b_kv),
        (_sds(params), codes, codes, scales, scales, vec, vec, scalar))


def _compile_spec_verify(model, params, b_kv: int, batch: int,
                         t_bucket: int):
    codes, scales, vec = _cache_sds(model.cfg, b_kv, batch, t_bucket)
    drafts = jax.ShapeDtypeStruct((batch, _SPEC_MAX_K), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return aot_compile(
        _build_spec_verify(model, b_kv),
        (_sds(params), codes, codes, scales, scales, vec, vec, vec,
         drafts, scalar, vec, scalar),
        donate_argnums=(1, 2, 3, 4, 5, 6))


def _container_dtype(cfg, b_kv: int) -> np.dtype:
    return np.dtype("int8") if b_kv < 16 else np.dtype(cfg.dtype)


def _cache_shapes(cfg, batch: int, t_bucket: int):
    """(codes, scales) shapes of a slot block: head-major codes
    [L, B, KV, dh, T] and scales [L, B, KV, 1, T].  B stays axis 1 (slot
    slices, row masks); KV next makes the kernel's [L, B*KV, ...] views
    reshapes; positions minor is the layout the TPU gives such an array
    anyway, so nothing relays it out (DESIGN.md §13)."""
    codes = (cfg.n_layers, batch, cfg.n_kv_heads, cfg.head_dim, t_bucket)
    return codes, codes[:3] + (1, t_bucket)


def _cache_sds(cfg, b_kv: int, batch: int, t_bucket: int):
    shape, s_shape = _cache_shapes(cfg, batch, t_bucket)
    codes = jax.ShapeDtypeStruct(shape, _container_dtype(cfg, b_kv))
    scales = jax.ShapeDtypeStruct(s_shape, jnp.float32)
    vec = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return codes, scales, vec


def _compile_prefill(model, params, b_kv: int, s_bucket: int,
                     t_bucket: int, batch: int):
    codes, scales, vec = _cache_sds(model.cfg, b_kv, batch, t_bucket)
    tokens = jax.ShapeDtypeStruct((1, s_bucket), jnp.int32)
    li = jax.ShapeDtypeStruct((1,), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return aot_compile(
        _build_prefill(model, b_kv),
        (_sds(params), tokens, li, scalar, codes, codes, scales, scales,
         vec, vec),
        donate_argnums=(4, 5, 6, 7, 8, 9))


def _compile_fused(model, params, b_kv: int, batch: int, t_bucket: int):
    codes, scales, vec = _cache_sds(model.cfg, b_kv, batch, t_bucket)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return aot_compile(
        _build_fused_decode(model, b_kv),
        (_sds(params), codes, codes, scales, scales, vec, vec, vec,
         scalar, scalar),
        donate_argnums=(1, 2, 3, 4, 5, 6))


# ---------------------------------------------------------------------------
# engine internals
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _ClassState:
    """One QoS class's resolved operating point."""
    qos: QosClass
    b_hat: int
    b_eff: float                # mean agent bits (= b_hat when uniform)
    b_kv: int
    f: float
    f_server: float
    plan_key: tuple             # keys the materialized weight tree
    plan_bits: tuple
    solution: Any = None        # DecodeSolution / MixedDecodeSolution


@dataclasses.dataclass
class _Active:
    """One in-flight request occupying a decode slot."""
    req: DecodeRequest
    generated: List[int]
    admitted_s: float
    ttft_s: float
    last_emit_s: float
    itls: List[float]
    on_token: Optional[Callable]
    # host clock when the first / last token reached the host; stamped
    # only while metrics are on (the wall TTFT and TPOT histograms)
    first_wall_s: float = 0.0
    last_wall_s: float = 0.0


class _Group:
    """One (QoS class, cache bucket) slot block: a fixed-width batched
    cache of ``max_batch`` decode slots at cache length ``t_bucket``.

    All buffers are ON-DEVICE jax arrays (DESIGN.md §13) that persist
    across steps and are donated to every prefill/decode executable —
    the host never copies the cache.  Inactive rows hold pos=0/token=0:
    their (garbage, row-independent) computation never escapes the row,
    and the next admission overwrites the prompt span before position 0
    is ever attended.
    """

    def __init__(self, cfg, qos_name: str, t_bucket: int, max_batch: int,
                 b_kv: int):
        self.qos_name = qos_name
        self.t_bucket = int(t_bucket)
        cont = _container_dtype(cfg, b_kv)
        shape, s_shape = _cache_shapes(cfg, max_batch, t_bucket)
        self.k_codes = jnp.zeros(shape, cont)
        self.v_codes = jnp.zeros(shape, cont)
        self.k_scales = jnp.ones(s_shape, jnp.float32)
        self.v_scales = jnp.ones(s_shape, jnp.float32)
        self.pos = jnp.zeros((max_batch,), jnp.int32)
        self.tok = jnp.zeros((max_batch,), jnp.int32)
        self.slots: List[Optional[_Active]] = [None] * max_batch
        self.barrier_open = True

    def active_count(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class DecodeEngine:
    """Continuous-batching greedy decode over quantized KV-cache slots.

    ``classes`` are resolved at construction: per class one
    ``solve_decode`` (or ``allocate_bits_decode`` under
    ``mixed_precision``) picks (b̂ or a per-layer plan, f, f̃, b_kv); the
    class's agent partition is then materialized once as a
    fake-quantized weight tree (``runtime.qat.fake_quantize_agent``,
    memoized across classes on the plan key).  Construction raises
    ``ValueError`` for an infeasible class, matching
    ``BatchedCoInferenceEngine``.  ``auto=False`` skips the solve
    (default operating point b̂=8/b_kv=8 at max frequencies) so tests
    and calibration runs can pin operating points via
    :meth:`set_operating_point`.

    ``admission`` picks the scheduling policy on otherwise identical
    code: ``"continuous"`` admits into any free slot every step and
    retires mid-flight; ``"barrier"`` refills a slot block only once it
    has fully drained (the FIFO-barrier baseline the benchmark beats).

    ``eos_id`` (optional) retires a request at its first emission of
    that token: the fused chunk executable exits early once every live
    slot has hit it, and the host truncates the row's stream there.
    """

    def __init__(self, model, params, sysp: SystemParams, *,
                 classes: Sequence[QosClass],
                 max_batch: int = 4,
                 max_new_tokens: int = 16,
                 admission: str = "continuous",
                 mixed_precision: bool = False,
                 kv_ladder: "tuple[int, ...]" = (4, 8, 16),
                 kv_weight: float = 1.0,
                 b_emb: Optional[int] = None,
                 auto: bool = True,
                 lam: Optional[float] = None,
                 lam_kv: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 codesign_cache: Optional[CodesignCache] = None,
                 compile_cache: Optional[CompiledForwardCache] = None,
                 seq_bucket_base: int = DEFAULT_SEQ_BASE,
                 tracer=None, metrics=None):
        gap = decode_protocol_gap(model)
        if gap is not None:
            raise TypeError(f"{type(model).__name__} {gap}; the decode "
                            "engine needs the DecoderLM decode protocol "
                            "(DESIGN.md §12)")
        if admission not in ("continuous", "barrier"):
            raise ValueError(f"unknown admission policy {admission!r}")
        if not classes:
            raise ValueError("need at least one QoS class")
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.sysp = sysp
        self.split = self.cfg.split_layer
        self.max_batch = int(max_batch)
        self.max_new_tokens = int(max_new_tokens)
        self.admission = admission
        self.mixed_precision = bool(mixed_precision)
        self.kv_ladder = tuple(int(b) for b in kv_ladder)
        self.kv_weight = float(kv_weight)
        self.b_emb = b_emb
        self.eos_id = int(eos_id) if eos_id is not None else None
        self.seq_bucket_base = int(seq_bucket_base)
        self._axes = model.logical_axes()
        self.lam = float(lam) if lam is not None \
            else fit_lambda(params, self.split)
        self.lam_kv = float(lam_kv) if lam_kv is not None \
            else fit_kv_lambda(model, params)
        self.codesign_cache = codesign_cache if codesign_cache is not None \
            else CodesignCache()
        self.compile_cache = compile_cache if compile_cache is not None \
            else CompiledForwardCache()
        # observability (DESIGN.md §14): the no-op singletons by default,
        # so an uninstrumented engine pays nothing on the decode path
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else NULL_METRICS
        self._own_hits = self._own_misses = 0
        self._own_compile_hits = self._own_compile_misses = 0
        self._layer_stats: Optional[mp.LayerStats] = None
        self._weights: Dict[tuple, Any] = {}
        self._classes: Dict[str, _ClassState] = {}
        self._groups: Dict[tuple, _Group] = {}
        self._rr: List[tuple] = []          # round-robin group order
        self._queue: List[DecodeRequest] = []
        self._on_token: Dict[int, Optional[Callable]] = {}
        self._next_rid = 0
        self._clock = 0.0
        self._energy = 0.0
        self._prefills = 0
        self._rounds = 0
        self._served = 0
        self._cancelled = 0
        self._tokens_out = 0
        self._kv_bytes = 0
        self._kv_bytes_full = 0
        self._h2d = 0
        self._d2h = 0
        self._class_lat: Dict[str, Dict[str, list]] = {}
        for c in classes:
            if auto:
                self._resolve_class(c)
            else:
                self._classes[c.name] = None  # placeholder until set below
                self.set_operating_point(c.name, 8, 8, qos=c)
            self._class_lat[c.name] = {"ttft": [], "itl": [], "tokens": []}

    # ------------------------------------------------------------------
    # operating points
    # ------------------------------------------------------------------
    def flop_split(self, tokens: int):
        """(agent_flops, server_flops) for ``tokens`` positions —
        ``CoInferenceEngine.flop_split``'s exact accounting."""
        per_layer = self.cfg.active_param_count() / max(self.cfg.n_layers, 1)
        n_agent = 2.0 * per_layer * self.split * tokens
        n_server = 2.0 * per_layer * (self.cfg.n_layers - self.split) \
            * tokens
        return n_agent, n_server

    def layer_stats(self) -> mp.LayerStats:
        if self._layer_stats is None:
            self._layer_stats = mp.decoder_layer_stats(self.params,
                                                       self.split)
        return self._layer_stats

    def _resolve_class(self, c: QosClass) -> None:
        b_max = int(self.sysp.b_full)
        h0, m0 = self.codesign_cache.hits, self.codesign_cache.misses
        if self.mixed_precision:
            sol = self.codesign_cache.solve_decode_mixed(
                self.layer_stats(), self.lam_kv, self.sysp, c, b_max,
                b_emb=self.b_emb, kv_ladder=self.kv_ladder,
                kv_weight=self.kv_weight)
        else:
            sol = self.codesign_cache.solve_decode(
                self.lam, self.lam_kv, self.sysp, c, b_max,
                b_emb=self.b_emb, kv_ladder=self.kv_ladder,
                kv_weight=self.kv_weight)
        dh = self.codesign_cache.hits - h0
        dm = self.codesign_cache.misses - m0
        self._own_hits += dh
        self._own_misses += dm
        if dh:
            self.metrics.counter("codesign.cache_hits",
                                 engine="DecodeEngine", qos=c.name).inc(dh)
        if dm:
            self.metrics.counter("codesign.cache_misses",
                                 engine="DecodeEngine", qos=c.name).inc(dm)
        if sol is None:
            raise ValueError(
                f"QoS class {c.name!r} (T0={c.t0}, E0={c.e0}) is "
                "infeasible at every KV-cache bit-width "
                f"{self.kv_ladder}")
        target = mp.plan_from_bits(sol.inner.bits) \
            if self.mixed_precision else sol.b_hat
        self._classes[c.name] = None
        self.set_operating_point(c.name, target, sol.b_kv,
                                 f=sol.f, f_server=sol.f_server,
                                 qos=c, solution=sol)

    def set_operating_point(self, qos_name: str, target, b_kv: int, *,
                            f: Optional[float] = None,
                            f_server: Optional[float] = None,
                            qos: Optional[QosClass] = None,
                            solution=None) -> None:
        """Pin a class's (weights bit target, b_kv, frequencies).

        ``target`` is a uniform b̂ (int) or a :class:`QuantPlan` over the
        agent partition.  Must be called before the class's first
        admission — live slots hold caches produced under the previous
        weights.  Materialized weight trees are memoized on the plan
        key, so classes sharing a plan share one tree.
        """
        if qos is None:
            prev = self._classes.get(qos_name)
            if prev is None:
                raise KeyError(f"unknown QoS class {qos_name!r}")
            qos = prev.qos
        b_kv = int(b_kv)
        if b_kv < 2:
            raise ValueError(f"b_kv={b_kv} below the 2-bit floor")
        if isinstance(target, QuantPlan):
            plan_key = target.key()
            b_eff = float(target.mean_bits(self.split))
            b_hat = int(round(b_eff))
            plan_bits = tuple(target.layer_bit_list(self.split))
            qcfg: Any = target
        else:
            b_hat = int(target)
            b_eff = float(b_hat)
            plan_key = ("uniform", b_hat)
            plan_bits = ()
            qcfg = QuantConfig(bits=b_hat, scheme="uniform",
                               granularity="per-channel")
        if plan_key not in self._weights:
            self._weights[plan_key] = self._materialize(plan_key, qcfg)
        self._classes[qos_name] = _ClassState(
            qos=qos, b_hat=b_hat, b_eff=b_eff, b_kv=b_kv,
            f=float(f) if f is not None else self.sysp.f_max,
            f_server=float(f_server) if f_server is not None
            else self.sysp.f_server_max,
            plan_key=plan_key, plan_bits=plan_bits, solution=solution)

    def _materialize(self, plan_key: tuple, qcfg):
        """The fake-quantized weight tree of one plan (set-up), its
        device work finished inside the ``decode.materialize`` span."""
        with self.tracer.span("decode.materialize", plan=str(plan_key)):
            return jax.block_until_ready(fake_quantize_agent(
                self.params, self._axes, self.cfg, qcfg, ste=False))

    def solution_for(self, qos_name: str):
        """The class's decode codesign solution (None when pinned)."""
        return self._classes[qos_name].solution

    def b_kv_for(self, qos_name: str) -> int:
        return self._classes[qos_name].b_kv

    def class_params(self, qos_name: str):
        """The class's materialized (fake-quantized) weight tree — what
        the sequential reference must decode with for parity."""
        return self._weights[self._classes[qos_name].plan_key]

    # ------------------------------------------------------------------
    # executables
    # ------------------------------------------------------------------
    def _cached(self, key: tuple, build: Callable,
                plan: str = "", bucket: str = ""):
        cc = self.compile_cache
        h0, m0 = cc.hits, cc.misses
        if key in cc:
            exe = cc.get(key, build)
        else:
            # one XLA compile: traced + timed under its (plan, bucket)
            # attribution (DESIGN.md §14)
            with self.tracer.span("xla.compile", plan=plan, bucket=bucket):
                t0 = time.monotonic()
                exe = cc.get(key, build)
                self.metrics.histogram(
                    "compile.seconds", plan=plan,
                    bucket=bucket).observe(time.monotonic() - t0)
        dh, dm = cc.hits - h0, cc.misses - m0
        self._own_compile_hits += dh
        self._own_compile_misses += dm
        if dh:
            self.metrics.counter("compile.cache_hits",
                                 engine="DecodeEngine").inc(dh)
        if dm:
            self.metrics.counter("compile.cache_misses",
                                 engine="DecodeEngine").inc(dm)
        return exe

    def _prefill_exe(self, c: _ClassState, s_bucket: int, t_bucket: int):
        return self._cached(
            ("decode-prefill", self.cfg, s_bucket, t_bucket,
             self.max_batch, c.b_kv),
            lambda: _compile_prefill(self.model, self.params, c.b_kv,
                                     s_bucket, t_bucket, self.max_batch),
            plan=f"decode-prefill/bkv{c.b_kv}",
            bucket=f"{s_bucket}->{t_bucket}x{self.max_batch}")

    def _decode_exe(self, c: _ClassState, t_bucket: int):
        return self._cached(
            ("decode-fused", self.cfg, self.max_batch, t_bucket, c.b_kv),
            lambda: _compile_fused(self.model, self.params, c.b_kv,
                                   self.max_batch, t_bucket),
            plan=f"decode-fused/bkv{c.b_kv}",
            bucket=f"{t_bucket}x{self.max_batch}")

    def warmup(self, max_prompt: int, max_new: Optional[int] = None) -> int:
        """Precompile every reachable variant; returns the number of XLA
        compiles this triggered.  Prefill executables are keyed on the
        (prompt bucket, cache bucket) PAIR — the in-executable scatter
        makes the slot block's shape part of the graph — so the reachable
        set is every s <= t from the two ladders, plus one fused-chunk
        executable per cache bucket, times the classes' b_kv rungs.
        After a warmup covering the traffic's prompt/generation bounds,
        steady-state serving never compiles (asserted by tests and
        ``benchmarks/decode.py``)."""
        m0 = self._own_compile_misses
        mn = int(max_new) if max_new is not None else self.max_new_tokens
        for c in self._classes.values():
            t_rungs = seq_ladder(max_prompt + mn, self.seq_bucket_base)
            for t in t_rungs:
                self._decode_exe(c, t)
            for s in seq_ladder(max_prompt, self.seq_bucket_base):
                for t in t_rungs:
                    if t >= s:
                        self._prefill_exe(c, s, t)
        return self._own_compile_misses - m0

    # ------------------------------------------------------------------
    # queue API
    # ------------------------------------------------------------------
    def submit(self, tokens, qos: str,
               max_new_tokens: Optional[int] = None,
               arrival_s: Optional[float] = None,
               on_token: Optional[Callable] = None) -> int:
        """Queue a prompt; returns its request id.

        ``on_token(request_id, token, t_s)`` streams each generated
        token at its virtual emission time."""
        toks = np.asarray(tokens, np.int32).reshape(-1)
        if toks.size == 0:
            raise ValueError("empty prompt")
        if qos not in self._classes:
            raise KeyError(f"unknown QoS class {qos!r}")
        m = int(max_new_tokens) if max_new_tokens is not None \
            else self.max_new_tokens
        if m < 1:
            raise ValueError("max_new_tokens must be >= 1")
        rid = self._next_rid
        self._next_rid += 1
        arr = float(arrival_s) if arrival_s is not None else self._clock
        self._queue.append(DecodeRequest(
            request_id=rid, tokens=toks, qos=qos, max_new_tokens=m,
            arrival_s=arr, submitted_wall_s=time.monotonic()
            if self.metrics.enabled else 0.0))
        self._on_token[rid] = on_token
        self.tracer.instant("decode.submit", rid=rid)
        return rid

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        return sum(g.active_count() for g in self._groups.values())

    @property
    def clock_s(self) -> float:
        return self._clock

    def request_bucket(self, req: DecodeRequest) -> int:
        """A request's cache bucket — a pure function of its OWN prompt
        length and generation budget (never of its batch-mates), which
        is what makes batched and sequential reductions shape-identical
        and therefore bitwise comparable."""
        return int(seq_bucket(req.tokens.size + req.max_new_tokens,
                              self.seq_bucket_base))

    def cancel(self, request_id: int) -> Optional[DecodeResponse]:
        """Retire a request mid-decode (or drop it from the queue).

        Frees the slot immediately — the next admission reuses it —
        and returns the partial response; None if the id is unknown or
        already retired."""
        for i, r in enumerate(self._queue):
            if r.request_id == request_id:
                del self._queue[i]
                self._cancelled += 1
                self._on_token.pop(request_id, None)
                return DecodeResponse(
                    request_id=request_id, qos=r.qos,
                    tokens=np.zeros((0,), np.int32),
                    prompt_len=r.tokens.size,
                    b_kv=self._classes[r.qos].b_kv,
                    ttft_s=float("nan"), itl_mean_s=0.0,
                    finished_s=self._clock, cancelled=True)
        for g in self._groups.values():
            for i, act in enumerate(g.slots):
                if act is not None and act.req.request_id == request_id:
                    return self._retire(g, i, cancelled=True)
        return None

    # ------------------------------------------------------------------
    # supervisor hooks (DESIGN.md §15)
    # ------------------------------------------------------------------
    def fast_forward(self, t_s: float) -> None:
        """Advance the virtual clock to ``t_s`` (never backwards) — the
        supervisor's hook for billing fault wait time (backoff sleeps,
        server repair windows) on the same clock every modeled cost
        lands on."""
        self._clock = max(self._clock, float(t_s))

    def decode_round_cost(self, qos_name: str, t_bucket: int):
        """Public (seconds, joules) of one fused decode step for the
        class at cache bucket ``t_bucket`` — what the supervisor bills
        per token when it finishes a recovered request through the
        sequential reference instead of a slot block."""
        return self._round_cost(self._classes[qos_name], int(t_bucket))

    def snapshot_request(self, request_id: int) -> Optional[dict]:
        """Freeze one in-flight request into a host-side snapshot the
        sequential reference can resume bitwise.

        The per-slot slice of the group's device buffers IS the
        reference's batch-width-1 state: the kernels are row-independent,
        so slot ``s`` of a ``max_batch``-wide cache holds exactly what a
        width-1 run over the same request holds, and
        ``greedy_decode_reference(state=...)`` continues it
        token-for-token (the crash-recovery contract of DESIGN.md §15,
        proven in ``tests/test_fault_tolerance.py``).  Returns None for
        unknown, still-queued, or already-retired ids — only in-flight
        requests have cache state to save.
        """
        for g in self._groups.values():
            for slot, act in enumerate(g.slots):
                if act is None or act.req.request_id != request_id:
                    continue
                c = self._classes[act.req.qos]
                state = {
                    "k_codes": np.asarray(g.k_codes[:, slot:slot + 1]),
                    "v_codes": np.asarray(g.v_codes[:, slot:slot + 1]),
                    "k_scales": np.asarray(g.k_scales[:, slot:slot + 1]),
                    "v_scales": np.asarray(g.v_scales[:, slot:slot + 1]),
                    "pos": np.int32(np.asarray(g.pos)[slot]),
                    "last_token": np.int32(np.asarray(g.tok)[slot]),
                    "t_bucket": np.int32(g.t_bucket),
                }
                self._d2h += sum(getattr(v, "nbytes", 0)
                                 for v in state.values())
                return {"request": act.req, "qos": act.req.qos,
                        "b_kv": c.b_kv, "generated": list(act.generated),
                        "ttft_s": act.ttft_s, "itls": list(act.itls),
                        "last_emit_s": act.last_emit_s,
                        "t_bucket": int(g.t_bucket), "state": state}
        return None

    # ------------------------------------------------------------------
    # the decode loop
    # ------------------------------------------------------------------
    def step(self, max_decode_steps: Optional[int] = None) \
            -> List[DecodeResponse]:
        """One engine round: admit what the policy allows, then run one
        fused decode chunk for the next non-empty slot block
        (round-robin).  Returns the requests that retired.

        The chunk is clamped so it never overruns a scheduling boundary
        (see :meth:`_decode_round`); ``max_decode_steps`` caps it
        further — ``max_decode_steps=1`` reproduces the one-token-per-
        step cadence (used by tests that interleave cancel/step).
        """
        out: List[DecodeResponse] = []
        in_flight = self.in_flight
        # the span's self time is scheduling: the admission scan, group
        # choice, chunk length and live mask
        args = {"pending": len(self._queue), "in_flight": in_flight} \
            if self.tracer.enabled else {}
        with self.tracer.span("decode.step", **args):
            if in_flight == 0 and self._queue:
                nxt = min(r.arrival_s for r in self._queue)
                if nxt > self._clock:
                    self._clock = nxt     # fast-forward an idle engine
            self._admit(out)
            g = self._next_group()
            if g is not None:
                self._decode_round(g, out, max_decode_steps)
        return out

    def drain(self) -> List[DecodeResponse]:
        out: List[DecodeResponse] = []
        while self._queue or self.in_flight:
            out.extend(self.step())
        return out

    def _group_for(self, req: DecodeRequest) -> _Group:
        t = self.request_bucket(req)
        key = (req.qos, t)
        if key not in self._groups:
            self._groups[key] = _Group(self.cfg, req.qos, t,
                                       self.max_batch,
                                       self._classes[req.qos].b_kv)
            self._rr.append(key)
        return self._groups[key]

    def _admit(self, out: List[DecodeResponse]) -> None:
        admitted = True
        while admitted:
            admitted = False
            for qi, req in enumerate(self._queue):
                if req.arrival_s > self._clock:
                    continue
                g = self._group_for(req)
                if self.admission == "barrier" and not g.barrier_open:
                    continue
                slot = g.free_slot()
                if slot is None:
                    continue
                del self._queue[qi]
                self._prefill_into(g, slot, req, out)
                admitted = True
                break
        if self.admission == "barrier":
            for g in self._groups.values():
                if g.active_count() > 0:
                    g.barrier_open = False

    def _prefill_into(self, g: _Group, slot: int, req: DecodeRequest,
                      out: List[DecodeResponse]) -> None:
        c = self._classes[req.qos]
        p_len = req.tokens.size
        s_bucket = int(seq_bucket(p_len, self.seq_bucket_base))
        self.tracer.instant("decode.admit", rid=req.request_id,
                            qos=req.qos, slot=slot, prompt_len=p_len,
                            t_bucket=g.t_bucket)
        padded = np.zeros((1, s_bucket), np.int32)
        padded[0, :p_len] = req.tokens
        exe = self._prefill_exe(c, s_bucket, g.t_bucket)
        tr, m = self.tracer, self.metrics
        with tr.span("decode.prefill", rid=req.request_id, qos=req.qos,
                     s_bucket=s_bucket, t_bucket=g.t_bucket):
            with tr.span("decode.prefill.launch"):
                (tok0, g.k_codes, g.v_codes, g.k_scales, g.v_scales,
                 g.pos, g.tok) = exe(
                    self._weights[c.plan_key], jnp.asarray(padded),
                    jnp.asarray([p_len - 1], jnp.int32),
                    jnp.asarray(slot, jnp.int32), g.k_codes, g.v_codes,
                    g.k_scales, g.v_scales, g.pos, g.tok)
            with tr.span("decode.prefill.wait"):
                first = int(np.asarray(tok0)[0])
        t_host = time.monotonic() if m.enabled else 0.0
        # the only host<->device traffic an admission causes: the padded
        # prompt + two scalars in, the streamed first token out
        self._h2d += padded.nbytes + 8
        self._d2h += 4
        # bill the prefill at its bucketed workload, sequentially on the
        # virtual clock (prefills occupy the same accelerator)
        t_pre, e_pre = self._prefill_cost(c, s_bucket)
        self._clock += t_pre
        self._energy += e_pre
        self._prefills += 1
        shape = (self.cfg.n_layers, 1, g.t_bucket, self.cfg.n_kv_heads,
                 self.cfg.head_dim)
        self._kv_bytes += 2 * kv_cache_bytes(shape, c.b_kv)
        self._kv_bytes_full += int(2 * np.prod(shape)
                                   * self.sysp.b_full / 8.0)
        act = _Active(req=req, generated=[first],
                      admitted_s=self._clock,
                      ttft_s=self._clock - req.arrival_s,
                      last_emit_s=self._clock, itls=[],
                      on_token=self._on_token.pop(req.request_id, None),
                      first_wall_s=t_host, last_wall_s=t_host)
        g.slots[slot] = act
        if m.enabled:
            m.counter("decode.prefills", engine="DecodeEngine",
                      qos=req.qos).inc()
            m.counter("decode.h2d_bytes",
                      engine="DecodeEngine").inc(padded.nbytes + 8)
            m.counter("decode.d2h_bytes", engine="DecodeEngine").inc(4)
            m.histogram("decode.ttft_s", engine="DecodeEngine",
                        qos=req.qos).observe(t_host - req.submitted_wall_s)
        with tr.span("decode.emit") as sp:
            if act.on_token is not None:
                act.on_token(req.request_id, first, self._clock)
            if len(act.generated) >= req.max_new_tokens:
                out.append(self._retire(g, slot))
            if tr.enabled:
                sp.set(tokens=1)

    def _next_group(self) -> Optional[_Group]:
        for _ in range(len(self._rr)):
            key = self._rr.pop(0)
            self._rr.append(key)
            g = self._groups[key]
            if g.active_count() > 0:
                return g
        return None

    def _chunk_steps(self, g: _Group, t_round: float,
                     max_steps: Optional[int]) -> int:
        """How many fused steps this chunk may run: the tightest of the
        live slots' remaining budgets (the chunk then ends exactly at
        the first retirement), the next queued arrival (so admission
        timing matches one-token-at-a-time stepping), the fixed output
        block width, and the caller's cap."""
        rem = min(a.req.max_new_tokens - len(a.generated)
                  for a in g.slots if a is not None)
        k = max(1, min(rem, _CHUNK))
        future = [r.arrival_s for r in self._queue
                  if r.arrival_s > self._clock]
        if future:
            due = (min(future) - self._clock) / max(t_round, 1e-12)
            k = min(k, max(1, int(math.ceil(due))))
        if max_steps is not None:
            k = min(k, max(1, int(max_steps)))
        return k

    def _decode_round(self, g: _Group, out: List[DecodeResponse],
                      max_steps: Optional[int] = None) -> None:
        c = self._classes[g.qos_name]
        t_round, e_round = self._round_cost(c, g.t_bucket)
        k = self._chunk_steps(g, t_round, max_steps)
        live = np.zeros((self.max_batch,), np.int32)
        live_rows = [i for i, a in enumerate(g.slots) if a is not None]
        live[live_rows] = 1
        eos = self.eos_id if self.eos_id is not None else -1
        exe = self._decode_exe(c, g.t_bucket)
        tr = self.tracer
        with tr.span("decode.chunk", qos=g.qos_name,
                     live_rows=len(live_rows), t_bucket=g.t_bucket,
                     max_steps=k) as chunk:
            with tr.span("decode.chunk.launch"):
                (blk, steps, g.k_codes, g.v_codes, g.k_scales, g.v_scales,
                 g.tok, g.pos) = exe(
                    self._weights[c.plan_key], g.k_codes, g.v_codes,
                    g.k_scales, g.v_scales, g.tok, g.pos,
                    jnp.asarray(live), jnp.asarray(eos, jnp.int32),
                    jnp.asarray(k, jnp.int32))
            with tr.span("decode.chunk.wait"):
                blk = np.asarray(blk)
                steps = int(steps)
            if tr.enabled:
                chunk.set(steps=steps)
        # the only host<->device traffic a chunk causes, independent of
        # the cache size: the live mask + two scalars in, the token
        # block + step count out
        self._h2d += live.nbytes + 8
        self._d2h += blk.nbytes + 4
        m = self.metrics
        t_host = time.monotonic() if m.enabled else 0.0
        if m.enabled:
            m.counter("decode.chunks", engine="DecodeEngine",
                      qos=g.qos_name).inc()
            m.counter("decode.chunk_steps", engine="DecodeEngine",
                      qos=g.qos_name).inc(steps)
            m.counter("decode.h2d_bytes",
                      engine="DecodeEngine").inc(live.nbytes + 8)
            m.counter("decode.d2h_bytes",
                      engine="DecodeEngine").inc(blk.nbytes + 4)
            m.gauge("decode.live_rows", engine="DecodeEngine",
                    qos=g.qos_name).set(len(live_rows))
        clock0 = self._clock
        self._clock += steps * t_round
        self._energy += steps * e_round
        self._rounds += steps
        finished: List[int] = []
        done = set()
        emitted = 0
        with tr.span("decode.emit") as sp:
            for j in range(steps):
                t_emit = clock0 + (j + 1) * t_round
                for i in live_rows:
                    if i in done:
                        continue
                    act = g.slots[i]
                    tok_ij = int(blk[i, j])
                    act.generated.append(tok_ij)
                    act.itls.append(t_emit - act.last_emit_s)
                    act.last_emit_s = t_emit
                    emitted += 1
                    if act.on_token is not None:
                        act.on_token(act.req.request_id, tok_ij, t_emit)
                    if (self.eos_id is not None
                            and tok_ij == self.eos_id) \
                            or len(act.generated) >= act.req.max_new_tokens:
                        done.add(i)
                        finished.append(i)
                        act.last_wall_s = t_host
            for i in finished:
                out.append(self._retire(g, i))
            if tr.enabled:
                sp.set(tokens=emitted)

    def _retire(self, g: _Group, slot: int,
                cancelled: bool = False) -> DecodeResponse:
        act = g.slots[slot]
        g.slots[slot] = None
        g.pos = g.pos.at[slot].set(0)
        g.tok = g.tok.at[slot].set(0)
        if g.active_count() == 0:
            g.barrier_open = True
        c = self._classes[act.req.qos]
        itl = float(np.mean(act.itls)) if act.itls else 0.0
        if cancelled:
            self._cancelled += 1
        else:
            self._served += 1
            lat = self._class_lat[act.req.qos]
            lat["ttft"].append(act.ttft_s)
            lat["itl"].extend(act.itls)
            lat["tokens"].append(len(act.generated))
        self._tokens_out += len(act.generated)
        self.tracer.instant("decode.retire", rid=act.req.request_id,
                            qos=act.req.qos, tokens=len(act.generated),
                            cancelled=cancelled)
        m = self.metrics
        if m.enabled:
            m.counter("decode.retired", engine="DecodeEngine",
                      qos=act.req.qos).inc()
            m.counter("decode.tokens", engine="DecodeEngine",
                      qos=act.req.qos).inc(len(act.generated))
            # wall time per output token after the first, as the
            # benchmark defines it: (last - first token on the host) /
            # (n - 1), observed once per request at retirement
            n = len(act.generated)
            if not cancelled and n > 1:
                m.histogram("decode.tpot_s", engine="DecodeEngine",
                            qos=act.req.qos).observe(
                    (act.last_wall_s - act.first_wall_s) / (n - 1))
        return DecodeResponse(
            request_id=act.req.request_id, qos=act.req.qos,
            tokens=np.asarray(act.generated, np.int32),
            prompt_len=act.req.tokens.size, b_kv=c.b_kv,
            ttft_s=act.ttft_s, itl_mean_s=itl,
            finished_s=act.last_emit_s, cancelled=cancelled)

    # ------------------------------------------------------------------
    # billing
    # ------------------------------------------------------------------
    def _prefill_cost(self, c: _ClassState, s_bucket: int):
        n_a, n_s = self.flop_split(s_bucket)
        p = dataclasses.replace(self.sysp, n_flop_agent=n_a,
                                n_flop_server=n_s)
        t = float(agent_delay(c.b_eff, c.f, p)) \
            + float(server_delay(c.f_server, p))
        e = float(agent_energy(c.b_eff, c.f, p)) \
            + float(server_energy(c.f_server, p))
        return t, e

    def _round_cost(self, c: _ClassState, t_bucket: int):
        """One decode step over the FULL slot block: all ``max_batch``
        rows and the whole [L, B, T] cache read at b_kv are billed
        whether or not every slot is live — padding is compute/traffic
        the hardware really runs, which is exactly the waste continuous
        admission exists to avoid.  A fused chunk of k steps bills k of
        these."""
        n_a, n_s = self.flop_split(self.max_batch)
        kv_full = 2.0 * self.cfg.n_layers * self.max_batch * t_bucket \
            * self.cfg.n_kv_heads * self.cfg.head_dim \
            * (self.sysp.b_full / 8.0)
        p = dataclasses.replace(self.sysp, n_flop_agent=n_a,
                                n_flop_server=n_s, kv_bytes_full=kv_full)
        t = float(agent_delay(c.b_eff, c.f, p)) \
            + float(server_delay(c.f_server, p)) \
            + float(kv_delay(c.b_kv, p))
        e = float(agent_energy(c.b_eff, c.f, p)) \
            + float(server_energy(c.f_server, p)) \
            + float(kv_energy(c.b_kv, p))
        return t, e

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------
    def report(self) -> DecodeReport:
        classes = []
        for name, c in self._classes.items():
            lat = self._class_lat[name]
            itls = np.asarray(lat["itl"], np.float64)
            classes.append(ClassDecodeStats(
                qos=name, b_hat=c.b_hat, b_kv=c.b_kv,
                requests=len(lat["ttft"]),
                tokens=int(sum(lat["tokens"])),
                ttft_mean_s=float(np.mean(lat["ttft"]))
                if lat["ttft"] else 0.0,
                ttft_max_s=float(np.max(lat["ttft"]))
                if lat["ttft"] else 0.0,
                itl_mean_s=float(np.mean(itls)) if itls.size else 0.0,
                plan_bits=c.plan_bits,
                itl_p50_s=float(np.percentile(itls, 50))
                if itls.size else 0.0,
                itl_p95_s=float(np.percentile(itls, 95))
                if itls.size else 0.0))
        clock = max(self._clock, 1e-12)
        return DecodeReport(
            requests_served=self._served, cancelled=self._cancelled,
            tokens_generated=self._tokens_out, prefills=self._prefills,
            decode_rounds=self._rounds, total_delay_s=self._clock,
            total_energy_j=self._energy,
            throughput_tps=self._tokens_out / clock,
            throughput_rps=self._served / clock,
            admission=self.admission, classes=tuple(classes),
            kv_bytes=self._kv_bytes, kv_bytes_full=self._kv_bytes_full,
            codesign_hits=self._own_hits,
            codesign_misses=self._own_misses,
            compile_hits=self._own_compile_hits,
            compile_misses=self._own_compile_misses,
            compiled_variants=self.compile_cache.compiled_variants,
            h2d_bytes=self._h2d, d2h_bytes=self._d2h)


# ---------------------------------------------------------------------------
# the non-batched sequential reference
# ---------------------------------------------------------------------------

def greedy_decode_reference(model, weights, tokens, max_new_tokens: int, *,
                            b_kv: int,
                            seq_bucket_base: int = DEFAULT_SEQ_BASE,
                            reserve_tokens: Optional[int] = None,
                            compile_cache: Optional[
                                CompiledForwardCache] = None,
                            state: Optional[dict] = None,
                            return_state: bool = False):
    """One request, batch width 1 — the parity oracle.

    Decodes ``max_new_tokens`` greedy tokens from ``tokens`` under the
    same bucketing, quantized-cache step (``decode_step_q`` through the
    fused while-loop executable), and prefill+scatter as
    :class:`DecodeEngine`, at batch width 1; the engine must reproduce
    its output token-for-token at any batch width, admission order, and
    chunking (the while-loop iterations are isolated sub-computations,
    so where the host cuts a chunk cannot change bits).

    ``reserve_tokens`` fixes the cache bucket from a larger planned
    generation budget (``T = seq_bucket(prompt + reserve)``) so a
    decode can be split across calls: pass ``return_state=True``,
    serialize the returned state dict (plain numpy arrays), and resume
    by passing it back as ``state`` — the continuation is bitwise the
    uninterrupted run, which is how decode state survives an elastic
    re-mesh (``tests/test_elastic.py``).
    """
    cfg = model.cfg
    cache = compile_cache if compile_cache is not None \
        else CompiledForwardCache()
    cont = _container_dtype(cfg, b_kv)
    out: List[int] = []
    if state is None:
        toks = np.asarray(tokens, np.int32).reshape(-1)
        p_len = toks.size
        if p_len == 0:
            raise ValueError("empty prompt")
        t_bucket = int(seq_bucket(
            p_len + (reserve_tokens if reserve_tokens is not None
                     else max_new_tokens), seq_bucket_base))
        s_bucket = int(seq_bucket(p_len, seq_bucket_base))
        padded = np.zeros((1, s_bucket), np.int32)
        padded[0, :p_len] = toks
        shape, s_shape = _cache_shapes(cfg, 1, t_bucket)
        k_codes = jnp.zeros(shape, cont)
        v_codes = jnp.zeros(shape, cont)
        k_scales = jnp.ones(s_shape, jnp.float32)
        v_scales = jnp.ones(s_shape, jnp.float32)
        pos = jnp.zeros((1,), jnp.int32)
        tok = jnp.zeros((1,), jnp.int32)
        exe = cache.get(
            ("decode-prefill", cfg, s_bucket, t_bucket, 1, b_kv),
            lambda: _compile_prefill(model, weights, b_kv, s_bucket,
                                     t_bucket, 1))
        tok0, k_codes, v_codes, k_scales, v_scales, pos, tok = exe(
            weights, jnp.asarray(padded),
            jnp.asarray([p_len - 1], jnp.int32), jnp.asarray(0, jnp.int32),
            k_codes, v_codes, k_scales, v_scales, pos, tok)
        out.append(int(np.asarray(tok0)[0]))
        remaining = max_new_tokens - 1
    else:
        k_codes = jnp.asarray(np.asarray(state["k_codes"]))
        v_codes = jnp.asarray(np.asarray(state["v_codes"]))
        k_scales = jnp.asarray(np.asarray(state["k_scales"]))
        v_scales = jnp.asarray(np.asarray(state["v_scales"]))
        pos = jnp.asarray([int(state["pos"])], jnp.int32)
        tok = jnp.asarray([int(state["last_token"])], jnp.int32)
        t_bucket = int(state["t_bucket"])
        remaining = max_new_tokens
    live = jnp.ones((1,), jnp.int32)
    eos = jnp.asarray(-1, jnp.int32)
    while remaining > 0:
        exe = cache.get(
            ("decode-fused", cfg, 1, t_bucket, b_kv),
            lambda: _compile_fused(model, weights, b_kv, 1, t_bucket))
        blk, steps, k_codes, v_codes, k_scales, v_scales, tok, pos = exe(
            weights, k_codes, v_codes, k_scales, v_scales, tok, pos,
            live, eos, jnp.asarray(min(remaining, _CHUNK), jnp.int32))
        blk = np.asarray(blk)
        steps = int(steps)
        out.extend(int(blk[0, j]) for j in range(steps))
        remaining -= steps
    result = np.asarray(out, np.int32)
    if return_state:
        return result, {"k_codes": np.asarray(k_codes),
                        "v_codes": np.asarray(v_codes),
                        "k_scales": np.asarray(k_scales),
                        "v_scales": np.asarray(v_scales),
                        "pos": np.int32(np.asarray(pos)[0]),
                        "last_token": np.int32(np.asarray(tok)[0]),
                        "t_bucket": np.int32(t_bucket)}
    return result
