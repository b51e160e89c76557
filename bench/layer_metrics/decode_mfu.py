"""Model: operations of the decoded tokens (each at its live cache
length, dead slots excluded) over the device time of the fused decode
executables, against the chip's bf16 peak.  Moves ``tpot_p95_ms``."""

import trace_reduce


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    chunks = [(s, e) for name, s, e, _ in run.spans if name == "decode.chunk"]
    dev_s = trace_reduce.device_seconds_within(run.trace, chunks)
    if not dev_s:
        return None
    flops = sum(run.counts.decode_token_flops(run.arch, n)
                for _, n in trace_reduce.decoded_tokens(run))
    return 100.0 * flops / dev_s / run.peaks["flops_bf16"]
