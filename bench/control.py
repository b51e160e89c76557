#!/usr/bin/env python3
"""Readings that a cell's output limit is set from, in one process.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 11 12 ... \
        [--control-seeds 3] [--fault <fault>]

For each seed, exactly as a run does but with a short window at the
cell's own load: weights from the seed, the engine built and warmed, the
traffic served and drained, the program's state freed, and the sampled
served tokens scored against the float32 reference (``max_logit_gap``,
the program's reading).  On the first ``--control-seeds`` seeds it also
reads the control: the reference put in the program's place with every
matmul's operands rounded to fp8 (e4m3, per-tensor scale), the nearest
precision below the bfloat16 that the configuration states
(``bench/configs/*.json``, "numerics").  At each position of the same
prompts and served tokens, the token the fp8 pass puts first is scored
against the float32 reference the same way.  Both readings go through
the harness's own checks at the cell's limits (``bench/limits/
<workload>.json``), and each row says whether that side came out
correct.  With ``--fault``, the program runs with that fault planted
(``bench/faults.py``) and its side should come out not correct.

Executables are shared across seeds, so only the first seed compiles.
One JSON line per seed, then a summary line.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import faults
import run


def read_seed(cell, cfg, seed, seconds, compile_cache,
              control: bool) -> dict:
    t = time.monotonic()
    model, params = run.build_model(cfg, seed)
    eng = run.build_engine(cell, model, params, compile_cache=compile_cache)
    run.warm_up(eng, cell, seed)
    reqs, _, _, _ = run.serve_window(eng, cell, seed, seconds)
    del eng, model, params
    gc.collect()
    sample = run.check_sample(reqs, cell, seed)
    served, ctrl = run.reference_gaps(cell, seed, sample, control)
    checks = run.score(cell, reqs, served)
    row = {"seed": seed, "requests": len(reqs),
           "unfinished": checks["unfinished"]["value"],
           "tokens_checked": checks["served_tokens_checked"]["value"],
           "program_gap": checks["max_logit_gap"]["value"],
           "program_correct": run.passed(checks),
           "program_mismatches": int(sum((g > 0).sum() for g in served))}
    if control:
        cchecks = run.score(cell, reqs, ctrl)
        row["control_gap"] = cchecks["max_logit_gap"]["value"]
        row["control_correct"] = run.passed(cchecks)
        row["control_mismatches"] = int(sum((g > 0).sum() for g in ctrl))
    row["limit"] = checks["max_logit_gap"]["limit"]
    row["seconds"] = time.monotonic() - t
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", choices=faults.FAULTS)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    try:
        run.find_chips(cell.chips)
    except run.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    from repro.models import registry
    from repro.runtime.fastpath import CompiledForwardCache
    run.use_compile_cache()
    cfg = run.program_config(cell)
    if args.fault:
        faults.plant(args.fault, type(registry.build_model(cfg)))
    cc = CompiledForwardCache()
    rows = []
    for i, seed in enumerate(args.seeds):
        rows.append(read_seed(cell, cfg, seed, args.seconds, cc,
                              i < args.control_seeds))
        print(json.dumps(rows[-1]), flush=True)
    ctrl = [r for r in rows if "control_gap" in r]
    print(json.dumps({
        "summary": args.workload, "fault": args.fault, "seeds": len(rows),
        "lower": max(r["program_gap"] for r in rows),
        "program_correct_on": sum(r["program_correct"] for r in rows),
        "upper": min(r["control_gap"] for r in ctrl) if ctrl else None,
        "control_correct_on": sum(r["control_correct"] for r in ctrl)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
