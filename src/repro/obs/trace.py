"""Tracing: nested spans + instant events -> Chrome trace-event JSON.

The observability substrate of DESIGN.md §14.  A :class:`Tracer` records
duration spans (``ph="B"``/``"E"`` pairs) and instant events (``ph="i"``)
into a thread-safe in-process buffer and exports them as Chrome
trace-event JSON — the format Perfetto and ``chrome://tracing`` load
directly.  Timestamps come from an injectable clock so tests can produce
byte-stable traces (:class:`TickClock`) while production uses the wall
clock (:class:`MonotonicClock`).

Two additions put the spans beside the device.  While a tracer is
enabled and ``jax`` is already imported, every span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows the spans on its own clock next to the device ops (``obs`` never
imports jax itself).  And a tracer on the wall clock records each
Python garbage collection as a ``host.gc`` span.

Disabled tracing must be *free*: :data:`NULL_TRACER` is a module-level
singleton whose ``span()`` returns one preallocated no-op context
manager — no dict lookup, no allocation, no branch on a flag — so every
engine can take ``tracer=NULL_TRACER`` as its default and pay nothing
when observability is off (gated by ``benchmarks/obs_overhead.py``).
"""

from __future__ import annotations

import gc
import json
import sys
import threading
import time
import weakref


class MonotonicClock:
    """Wall clock: ``time.monotonic`` seconds (the production default)."""

    def __call__(self) -> float:
        return time.monotonic()


class TickClock:
    """Deterministic clock: starts at ``start`` and advances by a fixed
    ``tick`` on every read.  Traces stamped with it are byte-stable
    across runs — the test contract for trace golden files."""

    def __init__(self, start: float = 0.0, tick: float = 1e-3):
        self._now = float(start)
        self._tick = float(tick)

    def __call__(self) -> float:
        now = self._now
        self._now += self._tick
        return now


class _NullSpan:
    """Reusable no-op context manager handed out by :class:`NullTracer`."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        return None


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The disabled-tracing fast path: every method is a constant-time
    no-op returning preallocated objects.  ``enabled`` lets callers skip
    building expensive span *arguments* (string formatting, nbytes
    sums) when tracing is off."""

    enabled = False

    def span(self, name, tid=0, **args):
        return _NULL_SPAN

    def instant(self, name, tid=0, **args):
        return None

    @property
    def events(self):
        return ()


NULL_TRACER = NullTracer()


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` if jax is already imported, else
    None: this module never imports jax itself."""
    return getattr(getattr(sys.modules.get("jax"), "profiler", None),
                   "TraceAnnotation", None)


class _Span:
    """Context manager emitting a balanced B/E pair around a block, with
    a profiler annotation of the same name opened and closed just after
    each stamp."""

    __slots__ = ("_tracer", "_name", "_tid", "_args", "_end_args", "_ann")

    def __init__(self, tracer, name, tid, args):
        self._tracer = tracer
        self._name = name
        self._tid = tid
        self._args = args
        self._end_args = None
        self._ann = None

    def __enter__(self):
        self._tracer._emit("B", self._name, self._tid, self._args)
        annotation = _profiler_annotation()
        if annotation is not None:
            self._ann = annotation(self._name)
            self._ann.__enter__()
        return self

    def set(self, **args) -> None:
        """Arguments known only at the span's end, put on its E event
        (trace viewers merge them with the B event's)."""
        self._end_args = {**(self._end_args or {}), **args}

    def __exit__(self, *exc):
        # stamp, then close the annotation: both of its ends then lag
        # the span's by the same few microseconds
        self._tracer._emit("E", self._name, self._tid, self._end_args)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return False


class _GcSpans:
    """``gc.callbacks`` hook: one ``host.gc`` span per collection.

    A collection can start while the tracer's lock is held (an
    allocation inside ``_emit``), so the hook never takes that lock: it
    keeps its spans in a list of its own, each with the length the
    tracer's event list had when the collection began, and the tracer
    merges them at export.  It holds the event list, not the tracer, so
    a dropped tracer is freed and its finalizer removes the hook."""

    __slots__ = ("_events", "_open", "_ann", "spans")

    def __init__(self, events: list):
        self._events = events
        self._open = None
        self._ann = None
        self.spans: list = []

    def __call__(self, phase, info) -> None:
        if phase == "start":
            self._open = (len(self._events), time.monotonic(),
                          info["generation"])
            annotation = _profiler_annotation()
            if annotation is not None:
                self._ann = annotation("host.gc")
                self._ann.__enter__()
        elif self._open is not None:
            at, t0, generation = self._open
            self.spans.append((at, t0, time.monotonic(), generation))
            self._open = None
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None


def _unhook(hook) -> None:
    if hook in gc.callbacks:
        gc.callbacks.remove(hook)


class Tracer:
    """In-process span/event buffer with Chrome trace-event export.

    ``clock`` is any zero-arg callable returning seconds; timestamps are
    stored as integer microseconds (the trace-event unit).  Appends are
    guarded by a lock so engines running threaded stages may share one
    tracer.

    On the wall clock (:class:`MonotonicClock`, the default) the tracer
    also records Python's garbage collections as ``host.gc`` spans
    (``generation`` arg) in lane 0, for as long as it is alive.  A
    tracer on an injected clock records none: collections come at no
    fixed point of a run, and a test clock's traces are byte-stable.
    """

    enabled = True

    def __init__(self, clock=None, pid: int = 1):
        self._clock = clock if clock is not None else MonotonicClock()
        self._pid = int(pid)
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._gc = None
        if isinstance(self._clock, MonotonicClock):
            self._gc = _GcSpans(self._events)
            gc.callbacks.append(self._gc)
            weakref.finalize(self, _unhook, self._gc)

    # -- recording ----------------------------------------------------

    def _emit(self, ph, name, tid, args) -> None:
        ev = {
            "name": name,
            "ph": ph,
            "pid": self._pid,
            "tid": int(tid),
        }
        if args:
            ev["args"] = args
        # clock read under the lock: stamping and appending atomically
        # keeps ts non-decreasing within every lane even when threads
        # share one tracer (and one TickClock)
        with self._lock:
            ev["ts"] = int(round(self._clock() * 1e6))
            self._events.append(ev)

    def span(self, name: str, tid: int = 0, **args) -> _Span:
        """Open a duration span; use as ``with tracer.span("x", k=v) as
        sp:``, and ``sp.set(k=v)`` for arguments known at its end."""
        return _Span(self, name, tid, args or None)

    def instant(self, name: str, tid: int = 0, **args) -> None:
        """Record a zero-duration event (scope ``t`` = thread)."""
        ev = {
            "name": name,
            "ph": "i",
            "pid": self._pid,
            "tid": int(tid),
            "s": "t",
        }
        if args:
            ev["args"] = args
        with self._lock:
            ev["ts"] = int(round(self._clock() * 1e6))
            self._events.append(ev)

    # -- export -------------------------------------------------------

    @property
    def events(self) -> tuple:
        with self._lock:
            events = list(self._events)
        if self._gc is None or not self._gc.spans:
            return tuple(events)
        return tuple(self._with_gc(events, list(self._gc.spans)))

    def _with_gc(self, events: list, gcs: list) -> list:
        """``events`` with each ``host.gc`` span put where its
        collection began, its stamps kept between its neighbours in
        lane 0 (a collection runs between two events of its thread)."""
        out, i = [], 0
        for at, t0, t1, generation in gcs:
            out.extend(events[i:at])
            i = at
            lo = next((e["ts"] for e in reversed(out) if e["tid"] == 0),
                      None)
            hi = next((e["ts"] for e in events[at:] if e["tid"] == 0), None)
            b, e = int(round(t0 * 1e6)), int(round(t1 * 1e6))
            if lo is not None:
                b, e = max(b, lo), max(e, lo)
            if hi is not None:
                b, e = min(b, hi), min(e, hi)
            e = max(b, e)
            base = {"name": "host.gc", "pid": self._pid, "tid": 0}
            out.append({**base, "ph": "B", "ts": b,
                        "args": {"generation": generation}})
            out.append({**base, "ph": "E", "ts": e})
        out.extend(events[i:])
        return out

    def to_chrome_trace(self) -> dict:
        """The JSON-object form: Perfetto's preferred envelope."""
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def write(self, path) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_chrome_trace(), f, indent=1, sort_keys=True)
            f.write("\n")


def validate_chrome_trace(obj) -> list:
    """Schema-check a Chrome trace-event object; returns a list of
    problems (empty == valid).  Checked: the ``traceEvents`` envelope,
    required keys per event, non-decreasing ``ts`` within each
    ``(pid, tid)`` lane, and balanced/properly-nested B/E spans.  This
    is the checker CI's trace-smoke step runs via
    ``tools/trace_summary.py --validate``."""
    problems = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be an object with a 'traceEvents' list"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be a list"]
    last_ts: dict = {}
    stacks: dict = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        for key in ("name", "ph", "ts", "pid", "tid"):
            if key not in ev:
                problems.append(f"event {i}: missing required key {key!r}")
        ph = ev.get("ph")
        if ph not in ("B", "E", "i", "X", "C", "M"):
            problems.append(f"event {i}: unknown phase {ph!r}")
        ts, lane = ev.get("ts"), (ev.get("pid"), ev.get("tid"))
        if isinstance(ts, (int, float)):
            if lane in last_ts and ts < last_ts[lane]:
                problems.append(
                    f"event {i}: ts {ts} decreases in lane {lane}")
            last_ts[lane] = ts
        elif ts is not None:
            problems.append(f"event {i}: ts must be a number")
        if ph == "B":
            stacks.setdefault(lane, []).append(ev.get("name"))
        elif ph == "E":
            stack = stacks.setdefault(lane, [])
            if not stack:
                problems.append(f"event {i}: E without matching B "
                                f"in lane {lane}")
            else:
                stack.pop()
    for lane, stack in stacks.items():
        if stack:
            problems.append(
                f"lane {lane}: {len(stack)} unclosed span(s): {stack}")
    return problems
