"""The decode engine's span tree (DESIGN.md §14): every host moment of
``DecodeEngine.step`` inside a named span, the spans on the profiler's
clock, ``host.gc`` spans from Python's collector, and the wall-clock
TTFT/TPOT histograms."""

import gc
import json
import pathlib
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.cost_model import SystemParams
from repro.launch import serve
from repro.models.registry import build_model
from repro.obs import (NULL_METRICS, NULL_TRACER, MetricsRegistry, TickClock,
                       Tracer, validate_chrome_trace)
from repro.runtime import CompiledForwardCache, DecodeEngine, QosClass
from repro.runtime.speculative import SpeculativeDecodeEngine

ROOT = pathlib.Path(__file__).resolve().parent.parent
SYSP = SystemParams(n_flop_agent=6.4e10, n_flop_server=1.92e11)
QOS = QosClass("interactive", t0=3.5, e0=2.0)
# the launch / wait children of each engine call
CALLS = {"decode.prefill": ("decode.prefill.launch", "decode.prefill.wait"),
         "decode.chunk": ("decode.chunk.launch", "decode.chunk.wait"),
         "decode.spec_round": ("decode.spec_round.launch",
                               "decode.spec_round.wait")}


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0)), CompiledForwardCache()


def _serve(qwen, tracer=None, metrics=None, engine=DecodeEngine):
    model, params, cache = qwen
    eng = engine(model, params, SYSP, classes=[QOS], auto=False,
                 max_batch=2, max_new_tokens=5, compile_cache=cache,
                 tracer=tracer, metrics=metrics)
    eng.set_operating_point(QOS.name, 8, 8)
    rng = np.random.default_rng(7)
    for i in range(5):
        eng.submit(rng.integers(0, model.cfg.vocab_size,
                                size=int(rng.integers(4, 12))).astype(
                                    np.int32),
                   QOS.name, max_new_tokens=1 + i % 5)
    out = eng.drain()
    return eng, [r.tokens for r in sorted(out, key=lambda r: r.request_id)]


def _tree(events):
    """Closed spans as (name, B event, E event, parent name, children)."""
    stack, spans = [], []
    for ev in events:
        if ev["ph"] == "B":
            stack.append([ev["name"], ev, None, stack[-1][0]
                          if stack else None, []])
            if len(stack) > 1:
                stack[-2][4].append(ev["name"])
        elif ev["ph"] == "E":
            node = stack.pop()
            node[2] = ev
            spans.append(tuple(node))
    assert not stack
    return spans


@pytest.mark.parametrize("engine", [DecodeEngine, SpeculativeDecodeEngine])
def test_step_span_tree_under_tick_clock(qwen, engine, tmp_path):
    tr = Tracer(clock=TickClock())
    _serve(qwen, tracer=tr, engine=engine)
    path = tmp_path / "t.json"
    tr.write(path)
    assert validate_chrome_trace(json.loads(path.read_text())) == []
    spans = _tree(tr.events)
    calls = [s for s in spans if s[0] in CALLS]
    assert {s[0] for s in calls} >= {"decode.prefill"}
    assert len({s[0] for s in calls}) == 2       # prefill + chunk/round
    for name, b, e, parent, children in calls:
        # exactly one launch and one wait, in that order, and nothing else
        assert children == list(CALLS[name]), (name, children)
        assert parent == "decode.step"
        if name == "decode.chunk":
            assert e["args"]["steps"] >= 1
            assert e["args"]["steps"] <= b["args"]["max_steps"]
    steps = [s for s in spans if s[0] == "decode.step"]
    assert steps and all(s[3] is None for s in steps)
    for _, b, _, _, _ in steps:
        assert set(b["args"]) == {"pending", "in_flight"}
    emits = [s for s in spans if s[0] == "decode.emit"]
    assert emits and all(s[3] == "decode.step" for s in emits)
    # every token a request received was emitted inside a decode.emit
    retired = [ev for ev in tr.events if ev["name"] == "decode.retire"]
    assert sum(s[2]["args"]["tokens"] for s in emits) == \
        sum(ev["args"]["tokens"] for ev in retired)
    submits = [ev for ev in tr.events if ev["name"] == "decode.submit"]
    assert [ev["args"]["rid"] for ev in submits] == list(range(5))
    # set-up: the operating point's weight tree, outside every step
    assert [s[3] for s in spans if s[0] == "decode.materialize"] == \
        [None] * (2 if engine is SpeculativeDecodeEngine else 1)


def test_traced_tokens_equal_untraced_and_null_tracer_is_shared(qwen):
    eng, plain = _serve(qwen)
    assert eng.tracer is NULL_TRACER and eng.metrics is NULL_METRICS
    span = NULL_TRACER.span("decode.step", pending=1, in_flight=2)
    assert span is NULL_TRACER.span("decode.chunk")
    with span as sp:
        assert sp.set(steps=3) is None
    assert NULL_TRACER.events == ()
    _, traced = _serve(qwen, tracer=Tracer(), metrics=MetricsRegistry())
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)


def test_wall_clock_ttft_and_tpot_histograms(qwen):
    m = MetricsRegistry()
    _, toks = _serve(qwen, metrics=m)
    snap = m.snapshot()
    assert "decode.itl_s" not in snap
    ttft = snap["decode.ttft_s"]["series"]
    tpot = snap["decode.tpot_s"]["series"]
    assert sum(s["count"] for s in ttft) == len(toks)
    # one TPOT per request with more than one token, none for the rest
    assert sum(s["count"] for s in tpot) == sum(1 for t in toks
                                                if len(t) > 1)
    for s in ttft + tpot:
        assert 0.0 < s["sum"] < 60.0          # seconds of host wall time


def _full_collections(events):
    """(B, E) of the generation-2 ``host.gc`` spans (a young collection
    may start on its own at any allocation)."""
    spans = [(b, e) for b, e in zip(events, events[1:])
             if b["name"] == e["name"] == "host.gc" and b["ph"] == "B"]
    return [(b, e) for b, e in spans if b["args"] == {"generation": 2}]


def test_forced_collection_leaves_a_host_gc_span():
    tr = Tracer()
    with tr.span("outer"):
        gc.collect()
    evs = list(tr.events)
    (b, e), = _full_collections(evs)
    assert evs[0]["name"] == evs[-1]["name"] == "outer"
    assert evs[0]["ts"] <= b["ts"] <= e["ts"] <= evs[-1]["ts"]
    assert validate_chrome_trace(tr.to_chrome_trace()) == []


def test_collection_while_the_lock_is_held_does_not_deadlock():
    """A collection can start inside ``_emit`` with the tracer's lock
    held; the hook must not wait for that lock."""
    tr = Tracer()

    def collect_holding_the_lock():
        with tr._lock:
            gc.collect()
    t = threading.Thread(target=collect_holding_the_lock, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert len(_full_collections(list(tr.events))) == 1


def test_gc_hook_lives_as_long_as_the_tracer():
    before = len(gc.callbacks)
    tr = Tracer()
    assert len(gc.callbacks) == before + 1
    del tr
    assert len(gc.callbacks) == before
    # an injected clock records no collections (byte-stable traces)
    tick = Tracer(clock=TickClock())
    assert len(gc.callbacks) == before
    with tick.span("a"):
        gc.collect()
    assert [e["name"] for e in tick.events] == ["a", "a"]


def test_spans_reach_the_profiler_as_annotations(qwen, tmp_path):
    """While tracing, each span opens a profiler annotation of its name:
    the host plane holds one event per span."""
    tr = Tracer()
    _serve(qwen)                               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(qwen, tracer=tr)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    counts = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("decode.", "host.gc")):
                    counts[ev.name] = counts.get(ev.name, 0) + 1
    mine = {}
    for ev in tr.events:
        if ev["ph"] == "B":
            mine[ev["name"]] = mine.get(ev["name"], 0) + 1
    # collections are recorded for as long as the tracer lives, and
    # annotated only while the profiler runs
    assert counts.pop("host.gc", 0) <= mine.pop("host.gc", 0)
    assert counts == mine
    assert counts["decode.step"] > 0 and counts["decode.chunk.wait"] > 0


def test_obs_imports_without_jax():
    code = ("import sys, repro.obs as o; t = o.Tracer()\n"
            "with t.span('a') as s: s.set(k=1)\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'\n"
            "assert t.events[-1]['args'] == {'k': 1}\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={"PYTHONPATH": str(ROOT / "src"), "PATH": ""})
    assert p.returncode == 0, p.stderr


def test_serve_decode_trace_passes_the_validator(tmp_path):
    trace = tmp_path / "trace.json"
    rc = serve.run(["--arch", "qwen2-0.5b", "--smoke", "--decode",
                    "--requests", "3", "--max-new", "4",
                    "--trace-out", str(trace)])
    assert rc == 0
    p = subprocess.run([sys.executable, str(ROOT / "tools/trace_summary.py"),
                        str(trace), "--validate"], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0 and "OK" in p.stdout, p.stdout + p.stderr
    names = {e["name"] for e in json.loads(trace.read_text())["traceEvents"]}
    assert {"decode.step", "decode.emit", "decode.chunk.wait"} <= names
