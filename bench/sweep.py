#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell, in one process, to find
the highest rate the program sustains without a growing backlog (the
knee).  The cell's mix file then fixes its rate at about four fifths of
it.  The benchmark's own runs never run this.

    python3 bench/sweep.py --workload <name> --seconds <s> --rates 40 60 80 ...

One engine serves every rate in turn (weights from ``--seed``), each for
a window of ``--seconds`` with the mix's lengths, and prints per rate:
requests attempted and finished inside the window, the queue left at
the close, and the TTFT median and 95th percentile of the finished.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    try:
        run.find_chips(cell.chips)
    except run.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    run.use_compile_cache()
    model, params = run.build_model(run.program_config(cell), args.seed)
    eng = run.build_engine(cell, model, params)
    run.warm_up(eng, cell, args.seed)
    for rate in args.rates:
        at = dataclasses.replace(cell, mix={**cell.mix, "rate_rps": rate})
        reqs, t0, t_close, _ = run.serve_window(eng, at, args.seed,
                                                args.seconds)
        in_time = [r for r in reqs if r.done and r.times[-1] <= t_close]
        ttft = [r.times[0] - r.due for r in reqs if r.done]
        late = [r.submitted - r.due for r in reqs]
        print(json.dumps({
            "rate_rps": rate, "attempted": len(reqs),
            "finished_in_window": len(in_time),
            "open_at_close": len(reqs) - len(in_time),
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "lateness_max_ms": 1e3 * max(late)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
