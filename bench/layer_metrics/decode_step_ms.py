"""Compiled step: host-clock time of the fused decode chunks over the
decode steps they ran (sum of ``decode.chunk`` durations / sum of their
steps) in the traced window.  Moves ``tpot_p95_ms``."""


def read(run):
    chunks = [(e - s, a["max_steps"]) for name, s, e, a in run.spans
              if name == "decode.chunk"]
    steps = sum(k for _, k in chunks)
    return 1e3 * sum(d for d, _ in chunks) / steps if steps else None
