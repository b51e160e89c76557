"""From a profiler trace and the program's spans to what the per-layer
readers read.

``reduce_xplane`` turns the JAX profiler's ``.xplane.pb`` into a small
JSON-able dict: the device operations (name, start, duration, program),
on the host's ``time.monotonic`` clock, and the traced window.  The clock
is aligned through one ``TraceAnnotation`` the harness records at a known
monotonic time.  Everything after that works on the reduced dict, so a
test can run every reader on a small recorded trace.
"""

from __future__ import annotations

import glob
import os

CLOCK_MARK = "bench.clock"
# how a Pallas kernel shows in an op's HLO text on the TPU
KERNEL = 'custom_call_target="tpu_custom_call"'


def reduce_xplane(trace_dir: str, mark_mono_s: float, window: tuple) -> dict:
    """Device ops of every device plane, times in monotonic seconds.

    ``mark_mono_s`` is ``time.monotonic()`` read just before the
    ``CLOCK_MARK`` annotation was entered; ``window`` the traced
    (start, end) on the same clock.  Ops come from the device line that
    holds the individual operations ("XLA Ops"), whose event names are
    the HLO instruction text; each op is kept as (instruction name,
    start, duration, is a Pallas kernel), a kernel being a custom call
    to "tpu_custom_call".  The line nests ops (a while loop spans its
    body's ops).
    """
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    offset = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == CLOCK_MARK:
                    offset = ev.start_ns * 1e-9 - mark_mono_s
    if offset is None:
        raise ValueError(f"trace holds no {CLOCK_MARK!r} annotation")
    devices = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            ops = []
            for ev in line.events:
                text = ev.name
                cut = text.find(" = ")
                ops.append((text[:cut] if cut > 0 else text,
                            ev.start_ns * 1e-9 - offset,
                            ev.duration_ns * 1e-9,
                            KERNEL in text))
            devices[plane.name] = ops
    return {"window": list(window), "devices": devices}


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, start+dur) intervals, clipped to
    [lo, hi)."""
    ivs = sorted((max(s, lo), min(s + d, hi)) for s, d in intervals)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in ivs:
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_seconds(trace: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    lo, hi = trace["window"]
    devs = list(trace["devices"].values())
    if not devs:
        return 0.0
    return sum(union_seconds([(o[1], o[2]) for o in ops], lo, hi)
               for ops in devs) / len(devs)


def ops_within(ops, spans) -> list:
    """The ops that start inside one of the (start, end) host spans."""
    spans = sorted(spans)
    out, j = [], 0
    for op in sorted(ops, key=lambda o: o[1]):
        while j < len(spans) and spans[j][1] < op[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= op[1] <= spans[j][1]:
            out.append(op)
    return out


def device_seconds_within(trace: dict, spans, keep=None) -> float:
    """Device seconds (union of their intervals) of the ops that start
    inside the host ``spans`` ((start, end) pairs), averaged over the
    devices; ``keep(op)`` selects ops."""
    devs = list(trace["devices"].values())
    if not devs:
        return 0.0
    total = 0.0
    for ops in devs:
        sel = [(o[1], o[2]) for o in ops_within(ops, spans)
               if keep is None or keep(o)]
        total += union_seconds(sel, float("-inf"), float("inf"))
    return total / len(devs)


def decoded_tokens(run):
    """(request, context length) of every token a decode step produced
    inside the traced window: the k-th served token (k >= 1) of a
    prompt of P attends over P + k cache positions."""
    lo, hi = run.window
    for r in run.requests:
        for k, t in enumerate(r.times):
            if k >= 1 and lo <= t <= hi:
                yield r, len(r.prompt) + k


def self_seconds(ops) -> list:
    """(op, seconds) with each op's time less that of the ops nested
    directly inside it (same device line, properly nested)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [o[2] for o in ops]
    stack = []
    for i in order:
        s = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return list(zip(ops, own))
