"""Speculative co-inference: quantized agent drafts, server verifies
(DESIGN.md §16).

The PR-6/7 decode stack pays one full co-inference round — agent
partition forward, boundary uplink, server partition forward, cache
stream — per generated token.  This module amortizes the per-round
overheads over several tokens: the agent partition, fake-quantized at a
*draft* bit-width ``b_draft`` below the class operating point, greedily
drafts ``k`` tokens per round; the tokens and the boundary hidden state
go up once; the server-side verify pass checks all ``k`` against the
target operating point with standard longest-accepted-prefix rollback.
Acceptance rate is a function of the draft distortion ``D^U(b_draft)``,
which makes ``(b_draft, k)`` codesign variables alongside (b̂, f, f̃,
b_kv) — ``codesign.solve_speculative`` picks the joint point that
minimizes the distortion bound per *expected delivered token*.

Three commitments, on top of :class:`~.decode_engine.DecodeEngine`'s
four:

1.  **Bitwise parity, structurally.**  Rollback is realized as
    *commit-on-verify*: draft steps carry the KV cache functionally
    inside their executable and discard it, so speculative state never
    touches the canonical slot buffers.  The verify executable is a
    chain of *target* ``decode_step_q`` steps with per-row early exit —
    every token it feeds is a delivered-stream token, so every cache
    entry it commits is exactly what ``greedy_decode_reference``
    writes.  The draft influences only how many verify iterations run
    and what the round bills, never the bits (the §7/§12 house
    invariant, extended).  There is no truncation step because nothing
    speculative is ever committed.

2.  **Billed at the paper's round model.**  The virtual clock charges
    ``cost_model.speculative_round_delay``: ``k`` cheap drafts pinned
    at ``f_max``, ONE batched verify forward at the class operating
    point (decode forwards are weight-stream bound, so the ``k + 1``
    positions under one weight pass bill as a single per-token forward
    — that amortization is the speculative win), one uplink, ``k + 1``
    cache streams, and the rejected entries as rollback traffic.  The
    executed-vs-billed separation is the same one the whole repo uses
    (wall measurement lives in ``benchmarks/speculative.py``).

3.  **Supervision for free.**  Slots, groups, snapshots, cancel and
    retirement are inherited unchanged; rounds are atomic between
    ``step()`` calls and ``generated`` only ever holds verified tokens,
    so ``ServingSupervisor`` snapshots at round boundaries resume
    bitwise through the sequential reference, and rejected draft work
    is never billed twice (it was never delivered).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import mixed_precision as mp
from repro.core.cost_model import (SystemParams, speculative_round_delay,
                                   speculative_round_energy)
from repro.core.quantization import QuantConfig
from repro.kernels.bucketing import seq_ladder
from repro.obs import ReportBase

from .decode_engine import (_SPEC_MAX_K, DecodeEngine, DecodeResponse,
                            _ClassState, _compile_spec_round, _Group)
from .serve_engine import QosClass

__all__ = [
    "SpecRoundStats",
    "SpeculativeDecodeEngine",
    "SPEC_DRAFT_LADDER",
    "SPEC_LOOKAHEAD_MENU",
]

# the realizable draft/lookahead menus the codesign enumerates — the
# speculative analog of the KV container ladder
SPEC_DRAFT_LADDER = (2, 4, 8)
SPEC_LOOKAHEAD_MENU = (2, 4, 8)


@dataclasses.dataclass(frozen=True)
class SpecRoundStats(ReportBase):
    """Whole-run draft/verify aggregates of a speculative engine."""
    rounds: int                 # verify rounds executed
    drafted: int                # draft tokens proposed (live rows × k)
    accepted: int               # drafts the verifier accepted
    delivered: int              # tokens delivered by verify rounds
    acceptance_rate: float      # accepted / drafted
    accepted_per_round: float   # mean accepted prefix length per row
    tokens_per_round: float     # mean delivered per row per round (τ̂)


@dataclasses.dataclass
class _SpecState:
    """One class's resolved draft schedule."""
    b_draft: int
    k: int
    plan_key: tuple             # draft weight tree key in ``_weights``


class SpeculativeDecodeEngine(DecodeEngine):
    """Draft-then-verify decode over the inherited slot machinery.

    ``auto=True`` resolves each class through
    ``codesign.solve_speculative`` (or the mixed-precision analog),
    which picks ``(b̂ or plan, f, f̃, b_kv, b_draft, k)`` jointly;
    ``auto=False`` pins ``draft_bits``/``lookahead`` directly, and
    :meth:`set_operating_point` grows ``b_draft``/``k`` keyword
    arguments for tests.  Everything else — admission policies,
    cancellation, snapshots, reporting — is inherited.
    """

    def __init__(self, model, params, sysp: SystemParams, *,
                 classes: Sequence[QosClass],
                 draft_bits: int = 4,
                 lookahead: int = 4,
                 draft_ladder: "tuple[int, ...]" = SPEC_DRAFT_LADDER,
                 lookahead_menu: "tuple[int, ...]" = SPEC_LOOKAHEAD_MENU,
                 **kwargs):
        if not (1 <= int(lookahead) <= _SPEC_MAX_K):
            raise ValueError(f"lookahead={lookahead} outside "
                             f"[1, {_SPEC_MAX_K}]")
        # set before super().__init__: the base constructor resolves
        # classes through our overridden set_operating_point/_resolve_class
        self.draft_bits = int(draft_bits)
        self.lookahead = int(lookahead)
        self.draft_ladder = tuple(int(b) for b in draft_ladder)
        self.lookahead_menu = tuple(int(v) for v in lookahead_menu)
        self._spec: Dict[str, _SpecState] = {}
        self._spec_rounds = 0
        self._spec_row_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_delivered = 0
        super().__init__(model, params, sysp, classes=classes, **kwargs)

    # ------------------------------------------------------------------
    # operating points
    # ------------------------------------------------------------------
    def _resolve_class(self, c: QosClass) -> None:
        b_max = int(self.sysp.b_full)
        h0, m0 = self.codesign_cache.hits, self.codesign_cache.misses
        if self.mixed_precision:
            sol = self.codesign_cache.solve_speculative_mixed(
                self.layer_stats(), self.lam_kv, self.sysp, c, b_max,
                b_emb=self.b_emb, kv_ladder=self.kv_ladder,
                kv_weight=self.kv_weight, draft_ladder=self.draft_ladder,
                lookahead=self.lookahead_menu)
        else:
            sol = self.codesign_cache.solve_speculative(
                self.lam, self.lam_kv, self.sysp, c, b_max,
                b_emb=self.b_emb, kv_ladder=self.kv_ladder,
                kv_weight=self.kv_weight, draft_ladder=self.draft_ladder,
                lookahead=self.lookahead_menu)
        dh = self.codesign_cache.hits - h0
        dm = self.codesign_cache.misses - m0
        self._own_hits += dh
        self._own_misses += dm
        if dh:
            self.metrics.counter("codesign.cache_hits",
                                 engine="SpeculativeDecodeEngine",
                                 qos=c.name).inc(dh)
        if dm:
            self.metrics.counter("codesign.cache_misses",
                                 engine="SpeculativeDecodeEngine",
                                 qos=c.name).inc(dm)
        if sol is None:
            raise ValueError(
                f"QoS class {c.name!r} (T0={c.t0}, E0={c.e0}) is "
                "infeasible at every (b_kv, b_draft, k) in "
                f"{self.kv_ladder} x {self.draft_ladder} x "
                f"{self.lookahead_menu}")
        target = mp.plan_from_bits(sol.bits) if self.mixed_precision \
            else sol.b_hat
        self._classes[c.name] = None
        self.set_operating_point(c.name, target, sol.b_kv,
                                 f=sol.f, f_server=sol.f_server,
                                 qos=c, solution=sol,
                                 b_draft=sol.b_draft, k=sol.k)

    def set_operating_point(self, qos_name: str, target, b_kv: int, *,
                            b_draft: Optional[int] = None,
                            k: Optional[int] = None,
                            f: Optional[float] = None,
                            f_server: Optional[float] = None,
                            qos: Optional[QosClass] = None,
                            solution=None) -> None:
        """Base semantics plus the class's draft schedule (b_draft, k);
        omitted values keep the previous schedule (or the engine
        defaults on first resolution)."""
        prev = self._spec.get(qos_name)
        b_draft = int(b_draft) if b_draft is not None \
            else (prev.b_draft if prev else self.draft_bits)
        k = int(k) if k is not None \
            else (prev.k if prev else self.lookahead)
        if b_draft < 2:
            raise ValueError(f"b_draft={b_draft} below the 2-bit floor")
        if not (1 <= k <= _SPEC_MAX_K):
            raise ValueError(f"lookahead k={k} outside [1, {_SPEC_MAX_K}]")
        super().set_operating_point(qos_name, target, b_kv, f=f,
                                    f_server=f_server, qos=qos,
                                    solution=solution)
        dk = ("uniform", b_draft)
        if dk not in self._weights:
            self._weights[dk] = self._materialize(
                dk, QuantConfig(bits=b_draft, scheme="uniform",
                                granularity="per-channel"))
        self._spec[qos_name] = _SpecState(b_draft=b_draft, k=k,
                                          plan_key=dk)

    def spec_params(self, qos_name: str):
        """The class's materialized draft weight tree."""
        return self._weights[self._spec[qos_name].plan_key]

    def draft_schedule(self, qos_name: str) -> "tuple[int, int]":
        sp = self._spec[qos_name]
        return sp.b_draft, sp.k

    # ------------------------------------------------------------------
    # executables
    # ------------------------------------------------------------------
    def _spec_round_exe(self, c: _ClassState, t_bucket: int):
        return self._cached(
            ("spec-round", self.cfg, self.max_batch, t_bucket, c.b_kv),
            lambda: _compile_spec_round(self.model, self.params, c.b_kv,
                                        self.max_batch, t_bucket),
            plan=f"spec-round/bkv{c.b_kv}",
            bucket=f"{t_bucket}x{self.max_batch}")

    def warmup(self, max_prompt: int, max_new: Optional[int] = None) -> int:
        """Precompile every reachable variant: the prefill (prompt,
        cache)-bucket pairs exactly as the base engine, plus ONE fused
        spec-round (draft chain + verify chain in a single dispatch)
        executable per cache bucket — lookahead ``k`` is a runtime
        argument, so the post-warmup compile count is bounded by
        pairs × n_kv + rungs × n_kv, strictly inside the
        ladder × {draft, verify} budget of 2 × rungs × n_kv round
        executables."""
        m0 = self._own_compile_misses
        mn = int(max_new) if max_new is not None else self.max_new_tokens
        for c in self._classes.values():
            t_rungs = seq_ladder(max_prompt + mn, self.seq_bucket_base)
            for t in t_rungs:
                self._spec_round_exe(c, t)
            for s in seq_ladder(max_prompt, self.seq_bucket_base):
                for t in t_rungs:
                    if t >= s:
                        self._prefill_exe(c, s, t)
        return self._own_compile_misses - m0

    # ------------------------------------------------------------------
    # the speculative round
    # ------------------------------------------------------------------
    def _decode_round(self, g: _Group, out: List[DecodeResponse],
                      max_steps: Optional[int] = None) -> None:
        c = self._classes[g.qos_name]
        sp = self._spec[g.qos_name]
        live_rows = [i for i, a in enumerate(g.slots) if a is not None]
        rem = np.zeros((self.max_batch,), np.int32)
        for i in live_rows:
            rem[i] = (g.slots[i].req.max_new_tokens
                      - len(g.slots[i].generated))
        # drafting past the largest remaining budget is pure waste (the
        # verifier stops at rem), and ``max_steps`` caps delivered
        # tokens per row: max_steps=1 degenerates to plain decode
        # (n_draft=0, verify emits exactly one target token per row)
        n_draft = min(sp.k, max(int(rem[live_rows].max()) - 1, 0))
        if max_steps is not None:
            n_draft = min(n_draft, max(int(max_steps) - 1, 0))
        live = np.zeros((self.max_batch,), np.int32)
        live[live_rows] = 1
        eos = self.eos_id if self.eos_id is not None else -1
        exe = self._spec_round_exe(c, g.t_bucket)
        tr = self.tracer
        with tr.span("decode.spec_round", qos=g.qos_name,
                     live_rows=len(live_rows), t_bucket=g.t_bucket,
                     n_draft=n_draft):
            with tr.span("decode.spec_round.launch"):
                (blk, cnt, acc, g.k_codes, g.v_codes, g.k_scales,
                 g.v_scales, g.tok, g.pos) = exe(
                    self._weights[sp.plan_key], self._weights[c.plan_key],
                    g.k_codes, g.v_codes, g.k_scales, g.v_scales, g.tok,
                    g.pos, jnp.asarray(live),
                    jnp.asarray(n_draft, jnp.int32),
                    jnp.asarray(rem), jnp.asarray(eos, jnp.int32))
            with tr.span("decode.spec_round.wait"):
                blk = np.asarray(blk)
                cnt = np.asarray(cnt)
                acc = np.asarray(acc)
        # host traffic: masks + scalars in, the delivered block out
        # (drafts never leave the device — they live and die inside the
        # fused round executable)
        self._h2d += live.nbytes + rem.nbytes + 8
        self._d2h += blk.nbytes + cnt.nbytes + acc.nbytes
        n_live = len(live_rows)
        delivered = int(cnt[live_rows].sum())
        accepted = int(acc[live_rows].sum())
        tau_act = delivered / max(n_live, 1)
        t_round, e_round = self._spec_round_cost(c, sp, g.t_bucket,
                                                 n_draft, tau_act)
        self._clock += t_round
        self._energy += e_round
        self._rounds += 1
        self._spec_rounds += 1
        self._spec_row_rounds += n_live
        self._spec_drafted += n_draft * n_live
        self._spec_accepted += accepted
        self._spec_delivered += delivered
        m = self.metrics
        t_host = time.monotonic() if m.enabled else 0.0
        if m.enabled:
            m.counter("decode.spec_rounds",
                      engine="SpeculativeDecodeEngine",
                      qos=g.qos_name).inc()
            m.counter("decode.spec_drafted",
                      engine="SpeculativeDecodeEngine",
                      qos=g.qos_name).inc(n_draft * n_live)
            m.counter("decode.spec_accepted",
                      engine="SpeculativeDecodeEngine",
                      qos=g.qos_name).inc(accepted)
            m.counter("decode.h2d_bytes",
                      engine="SpeculativeDecodeEngine").inc(
                live.nbytes + rem.nbytes + 8)
            m.counter("decode.d2h_bytes",
                      engine="SpeculativeDecodeEngine").inc(
                blk.nbytes + cnt.nbytes + acc.nbytes)
            m.gauge("decode.live_rows",
                    engine="SpeculativeDecodeEngine",
                    qos=g.qos_name).set(n_live)
        # tokens land when the verify completes: the whole round's
        # output is delivered in one burst at the round boundary
        t_emit = self._clock
        finished: List[int] = []
        with tr.span("decode.emit") as emit:
            for i in live_rows:
                act = g.slots[i]
                for j in range(int(cnt[i])):
                    tok_ij = int(blk[i, j])
                    act.generated.append(tok_ij)
                    act.itls.append(t_emit - act.last_emit_s)
                    act.last_emit_s = t_emit
                    if act.on_token is not None:
                        act.on_token(act.req.request_id, tok_ij, t_emit)
                last = act.generated[-1]
                if (self.eos_id is not None and last == self.eos_id) \
                        or len(act.generated) >= act.req.max_new_tokens:
                    finished.append(i)
                    act.last_wall_s = t_host
            for i in finished:
                out.append(self._retire(g, i))
            if tr.enabled:
                emit.set(tokens=delivered)

    # ------------------------------------------------------------------
    # billing
    # ------------------------------------------------------------------
    def _spec_round_cost(self, c: _ClassState, sp: _SpecState,
                         t_bucket: int, n_draft: int, tau: float):
        """One speculative round at the PADDED workload, exactly as
        ``_round_cost`` pads the fused step: all ``max_batch`` rows and
        the full cache at ``b_kv`` are billed through
        ``cost_model.speculative_round_delay`` — ``n_draft`` drafts at
        ``f_max``, ONE batched verify weight pass over the ``n_draft +
        1`` positions, ``n_draft + 1`` cache streams, and the
        actually-rejected entries as rollback traffic."""
        n_a, n_s = self.flop_split(self.max_batch)
        kv_full = 2.0 * self.cfg.n_layers * self.max_batch * t_bucket \
            * self.cfg.n_kv_heads * self.cfg.head_dim \
            * (self.sysp.b_full / 8.0)
        p = dataclasses.replace(self.sysp, n_flop_agent=n_a,
                                n_flop_server=n_s, kv_bytes_full=kv_full)
        t = float(speculative_round_delay(
            c.b_eff, c.f, c.f_server, sp.b_draft, n_draft, tau, p,
            b_emb=self.b_emb, b_kv=c.b_kv))
        e = float(speculative_round_energy(
            c.b_eff, c.f, c.f_server, sp.b_draft, n_draft, tau, p,
            b_emb=self.b_emb, b_kv=c.b_kv))
        return t, e

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------
    def spec_stats(self) -> SpecRoundStats:
        rr = max(self._spec_row_rounds, 1)
        drafted = max(self._spec_drafted, 1)
        return SpecRoundStats(
            rounds=self._spec_rounds,
            drafted=self._spec_drafted,
            accepted=self._spec_accepted,
            delivered=self._spec_delivered,
            acceptance_rate=self._spec_accepted / drafted,
            accepted_per_round=self._spec_accepted / rr,
            tokens_per_round=self._spec_delivered / rr)
