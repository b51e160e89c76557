"""Scheduler: share of the decode slots that hold a live request, over
the fused decode chunks of the traced window, each chunk weighted by
its steps (``decode.chunk`` span: live_rows / max_batch).  Moves
``tokens_per_s``."""


def read(run):
    chunks = [(a["live_rows"], a["max_steps"]) for name, _, _, a in run.spans
              if name == "decode.chunk"]
    steps = sum(k for _, k in chunks)
    if not steps:
        return None
    return 100.0 * sum(n * k for n, k in chunks) / (steps * run.max_batch)
