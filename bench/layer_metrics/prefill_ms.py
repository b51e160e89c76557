"""Compiled step: mean host-clock duration of one admission's prefill
executable call, sync included (``decode.prefill`` spans of the traced
window).  Moves ``ttft_p95_ms``."""


def read(run):
    d = [e - s for name, s, e, _ in run.spans if name == "decode.prefill"]
    return 1e3 * sum(d) / len(d) if d else None
