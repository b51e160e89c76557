"""Model: operations of the true prompt tokens (padding excluded) over
the device time of the prefill executables, against the chip's bf16
peak.  Ops are attributed to a prefill by starting inside its
``decode.prefill`` span.  Moves ``ttft_p95_ms``."""

import trace_reduce


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    plen = {r.rid: len(r.prompt) for r in run.requests}
    pre = [(s, e, a["rid"]) for name, s, e, a in run.spans
           if name == "decode.prefill"]
    dev_s = trace_reduce.device_seconds_within(run.trace,
                                               [(s, e) for s, e, _ in pre])
    if not dev_s:
        return None
    flops = sum(run.counts.prefill_flops(run.arch, plen[rid])
                for _, _, rid in pre)
    return 100.0 * flops / dev_s / run.peaks["flops_bf16"]
