"""Scheduler: the engine's host time per ``DecodeEngine.step``, mean
over the ``decode.step`` spans of the traced window of the span's
duration less the time its ``decode.prefill.wait`` and
``decode.chunk.wait`` children cover.  Every engine call ends in a host
sync, so this is time in which the chip has nothing of the engine's to
run: scheduling, launches, token emission and retirement.  A program
without ``decode.step`` spans reads nothing.  Moves ``ttft_p95_ms``."""

WAITS = ("decode.prefill.wait", "decode.chunk.wait")


def read(run):
    steps = sorted((s, e) for name, s, e, _ in run.spans
                   if name == "decode.step")
    if not steps:
        return None
    waits = sorted((s, e) for name, s, e, _ in run.spans if name in WAITS)
    host, j = 0.0, 0
    for s, e in steps:
        host += e - s
        while j < len(waits) and waits[j][0] < s:
            j += 1
        while j < len(waits) and waits[j][1] <= e:
            host -= waits[j][1] - waits[j][0]
            j += 1
    return 1e3 * host / len(steps)
