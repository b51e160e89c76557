"""Kernel: the decode-attention kernel's share of its roofline.  The
least time is the larger of the bytes over HBM bandwidth and the
operations over the bf16 peak, for the work the algorithm needs: the
k/v codes and scales at each decoded token's live cache length, q read
and the output written, 4·len·dh operations per head
(``run.counts.decode_attn_work``).  At int8 codes that is about two
operations a byte, so HBM bandwidth bounds it.  Kernel time is the
device time of the Pallas kernel ops (``tpu_custom_call``; the decode
chunk runs no other) inside ``decode.chunk`` spans.
Moves ``tpot_p95_ms``."""

import trace_reduce

def read(run):
    if run.trace is None or run.peaks is None:
        return None
    chunks = [(s, e) for name, s, e, _ in run.spans if name == "decode.chunk"]
    kernel_s = trace_reduce.device_seconds_within(run.trace, chunks,
                                                  lambda op: op[3])
    if not kernel_s:
        return None
    flops = nbytes = 0
    for _, n in trace_reduce.decoded_tokens(run):
        f, b = run.counts.decode_attn_work(run.arch, n, run.code_bytes)
        flops += f
        nbytes += b
    least, _ = run.counts.least_time(flops, nbytes, run.peaks)
    return 100.0 * least / kernel_s
