"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device-op intervals / window).  Moves
``tokens_per_s`` in the closed-loop cells that report it; at a fixed
offered load the idle share only follows the load."""

import trace_reduce


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    lo, hi = run.window
    return 100.0 * (1.0 - trace_reduce.busy_seconds(run.trace) / (hi - lo))
