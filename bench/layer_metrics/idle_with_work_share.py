"""Device: share of the traced window in which the chip sat idle while
the engine held a submitted, unretired request.  Idle time is the gaps
in the union of device-op intervals (as ``trace_reduce.busy_seconds``
counts busy time, averaged over the devices).  It counts where it falls
inside a ``decode.step`` span, or in the gap before a step: the whole
gap when that step opened with ``in_flight`` > 0, else from the gap's
first ``decode.submit`` instant on (the gap before the window's first
step opens at the window's start; after its last step, time counts from
the first submit).  Unlike the whole idle share, it has a better side
at a fixed offered load.  A program without ``decode.step`` spans, or a
trace without device ops, reads nothing.  Moves ``ttft_p95_ms``."""

import trace_reduce


def work_intervals(run) -> list:
    """(start, end) stretches of the window in which the engine held
    work, by the rule above."""
    lo, hi = run.window
    steps = sorted((s, e, a.get("in_flight", 0)) for name, s, e, a
                   in run.spans if name == "decode.step")
    submits = sorted(t for name, t, _ in run.instants
                     if name == "decode.submit")
    out, prev, k = [], lo, 0
    for s, e, in_flight in steps + [(hi, hi, 0)]:
        while k < len(submits) and submits[k] < prev:
            k += 1
        if in_flight > 0:
            out.append((prev, s))
        elif k < len(submits) and submits[k] <= s:
            out.append((submits[k], s))
        out.append((s, e))
        prev = e
    return [(a, b) for a, b in out if b > a]


def _overlap(gaps, spans) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(gaps) and j < len(spans):
        a, b = max(gaps[i][0], spans[j][0]), min(gaps[i][1], spans[j][1])
        if b > a:
            total += b - a
        if gaps[i][1] < spans[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(ops, lo: float, hi: float) -> list:
    """The gaps in the union of the ops' intervals, clipped to [lo, hi)."""
    gaps, cur = [], lo
    for s, e in sorted((max(o[1], lo), min(o[1] + o[2], hi)) for o in ops):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    if not any(name == "decode.step" for name, _, _, _ in run.spans):
        return None
    lo, hi = run.window
    work = work_intervals(run)
    devs = list(run.trace["devices"].values())
    idle = sum(_overlap(idle_gaps(ops, lo, hi), work) for ops in devs)
    return 100.0 * idle / len(devs) / (hi - lo)
