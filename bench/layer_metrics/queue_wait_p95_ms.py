"""Scheduler: time from a request's due time to its admission (the
``decode.admit`` instant, just before its prefill), 95th percentile over
the requests due in the traced window.  Moves ``ttft_p95_ms``."""

import numpy as np


def read(run):
    admitted = {a["rid"]: t for name, t, a in run.instants
                if name == "decode.admit"}
    lo, hi = run.window
    waits = [admitted[r.rid] - r.due for r in run.requests
             if r.rid in admitted and lo <= r.due < hi]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
