"""Benchmark orchestrator: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,fig3,...]

Sections (paper artifact -> module):
    fig2    weight-magnitude exponential fit        weight_stats.py
    fig3    output vs parameter distortion          distortion.py
    fig4    distortion-rate bounds vs BA            rd_bounds.py
    fig5-8  CIDEr vs (T0, E0), 4 schemes            codesign_sweep.py
    table1  coarse frequency profiles               testbed_profiles.py
    kernels quantized-matmul TPU economics          kernel_bench.py
    serve   batched co-inference throughput         serve_throughput.py
            (also writes BENCH_serve.json at the repo root: req/s,
             batch size, bit-width, measured distortion — the
             machine-readable perf record diffed across PRs)
    mixed   per-layer bit allocation vs uniform     mixed_precision_sweep.py
    adaptive static/oracle/adaptive serving on a     adaptive_serve.py
            dynamic link/thermal/battery trace
            (also writes BENCH_adaptive.json at the repo root)
    fastpath eager vs AOT-compiled serving wall      fastpath.py
            clock + compile-count bound
            (also writes BENCH_fastpath.json at the repo root; raises
             on acceptance or throughput regression)
    fleet   joint vs equal-split shared-server       fleet.py
            allocation across heterogeneous agents
            (also writes BENCH_fleet.json at the repo root; raises if
             joint stops beating equal-split or the single-agent fleet
             loses bitwise identity)
    decode  continuous-batching vs FIFO-barrier      decode.py
            greedy decode over a quantized KV cache
            (also writes BENCH_decode.json at the repo root; raises if
             continuous admission stops beating the barrier, decode
             parity breaks, or warm traffic compiles)
    obs_overhead decode tok/s traced vs untraced      obs_overhead.py
            (also writes BENCH_obs.json at the repo root; raises if
             enabled tracing costs more than 3%, the disabled no-op
             path is not free, or tracing perturbs a single token)
    chaos   supervised vs bare decode under a seeded  chaos.py
            fault trace (outages, crashes)
            (also writes BENCH_chaos.json at the repo root; raises if
             the supervisor stops beating the bare engine, loses or
             duplicates tokens, recovered streams break bitwise
             parity, or the clean-trace pass-through costs over 3%)
    speculative quantized-draft/verify rounds vs      speculative.py
            fused decode across the (b_draft, k) grid
            (also writes BENCH_spec.json at the repo root; raises if
             speculation stops beating fused decode on modeled tok/s
             at the chosen point, any grid point loses bitwise parity,
             the codesign stops preferring the speculative solution,
             or warm traffic compiles)
"""

from __future__ import annotations

import argparse
import datetime
import json
import pathlib
import subprocess
import sys
import time

from . import (adaptive_serve, chaos, codesign_sweep, decode, distortion,
               fastpath, fleet, kernel_bench, mixed_precision_sweep,
               obs_overhead, rd_bounds, serve_throughput, speculative,
               testbed_profiles, weight_stats)
from .common import banner
from repro.launch.compile_cache import enable_compile_cache

SECTIONS = {
    "fig2": ("Fig. 2  weight statistics", weight_stats.run),
    "fig3": ("Fig. 3  distortion approximation", distortion.run),
    "fig4": ("Fig. 4  rate-distortion bounds", rd_bounds.run),
    "fig5-8": ("Figs 5-8  joint co-design sweeps", codesign_sweep.run),
    "table1": ("Table I  coarse frequency profiles", testbed_profiles.run),
    "kernels": ("Kernels  quantized matmul", kernel_bench.run),
    "serve": ("Serving  batched vs sequential throughput",
              serve_throughput.run),
    "mixed": ("Mixed precision  allocated plans vs uniform b̂",
              mixed_precision_sweep.run),
    "adaptive": ("Adaptive serving  static vs oracle vs adaptive on a "
                 "dynamic trace", adaptive_serve.run),
    "fastpath": ("Fast path  eager vs compiled serving wall clock",
                 fastpath.run),
    "fleet": ("Fleet  joint vs equal-split shared-server allocation",
              fleet.run),
    "decode": ("Decode  continuous-batching vs FIFO-barrier over a "
               "quantized KV cache", decode.run),
    "obs_overhead": ("Observability  decode tok/s traced vs untraced "
                     "(3% gate, bitwise parity)", obs_overhead.run),
    "chaos": ("Chaos  supervised vs bare decode under a seeded fault "
              "trace", chaos.run),
    "speculative": ("Speculative  quantized drafts vs fused decode at a "
                    "matched operating point", speculative.run),
}


# the one number per section worth tracking across commits: the first
# of these keys present in the section's result dict lands in
# BENCH_history.jsonl
_METRIC_KEYS = ("speedup", "throughput_ratio", "ratio", "tps",
                "throughput_tps", "acceptance_ok")

# BENCH_history.jsonl row schema: bumped to 2 when the rows gained
# explicit schema_version/units fields and the optional metrics
# snapshot (DESIGN.md §14); v1 rows (no schema_version key) predate it
_HISTORY_SCHEMA_VERSION = 2

# units for each trackable metric, so a history row is interpretable
# without chasing the producing section's source
_METRIC_UNITS = {
    "speedup": "ratio", "throughput_ratio": "ratio", "ratio": "ratio",
    "tps": "tokens/s", "throughput_tps": "tokens/s",
    "acceptance_ok": "bool",
}


def _git_sha() -> "str | None":
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=pathlib.Path(__file__).resolve().parent.parent,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except OSError:
        return None


def _key_metric(result) -> "tuple[str, object] | None":
    if not isinstance(result, dict):
        return None
    for k in _METRIC_KEYS:
        if k in result:
            return k, result[k]
    # one level down covers sections that nest (e.g. throughput tables)
    for outer, v in result.items():
        if isinstance(v, dict):
            for k in _METRIC_KEYS:
                if k in v:
                    return f"{outer}.{k}", v[k]
    return None


def append_history(section: str, result, seconds: float,
                   path: "pathlib.Path | None" = None) -> None:
    """Append one line per section run to ``BENCH_history.jsonl`` at the
    repo root: section, its key metric, the git SHA, wall seconds.  An
    append-only log (never rewritten, unlike the BENCH_*.json records),
    so perf across the PR stack stays greppable without archaeology."""
    if path is None:
        path = pathlib.Path(__file__).resolve().parent.parent \
            / "BENCH_history.jsonl"
    metric = _key_metric(result)
    entry = {
        "schema_version": _HISTORY_SCHEMA_VERSION,
        "ts": datetime.datetime.now(datetime.timezone.utc)
        .strftime("%Y-%m-%dT%H:%M:%SZ"),
        "git_sha": _git_sha(),
        "section": section,
        "metric": metric[0] if metric else None,
        "value": metric[1] if metric else None,
        "units": _METRIC_UNITS.get(
            metric[0].rsplit(".", 1)[-1]) if metric else None,
        "seconds": round(seconds, 3),
    }
    # sections that serve through instrumented engines may attach a
    # MetricsRegistry snapshot under "metrics" (DESIGN.md §14) — carry
    # it onto the history row so counter/histogram series stay greppable
    # across the PR stack alongside the headline number
    if isinstance(result, dict) and isinstance(result.get("metrics"),
                                               dict):
        entry["metrics"] = result["metrics"]
    with path.open("a", encoding="utf-8") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of sections (default: all)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip appending to BENCH_history.jsonl")
    args = ap.parse_args(argv)
    picks = args.only.split(",") if args.only else list(SECTIONS)
    enable_compile_cache()

    t0 = time.monotonic()
    failures = []
    for key in picks:
        title, fn = SECTIONS[key]
        banner(f"[{key}] {title}")
        t_sec = time.monotonic()
        try:
            result = fn()
        except Exception as e:  # noqa: BLE001 - keep the harness going
            failures.append((key, repr(e)))
            print(f"!! section {key} failed: {e!r}")
        else:
            if not args.no_history:
                append_history(key, result, time.monotonic() - t_sec)
    dt = time.monotonic() - t0
    print(f"\n{'=' * 72}\nbenchmarks done in {dt / 60:.1f} min; "
          f"{len(picks) - len(failures)}/{len(picks)} sections ok")
    for key, err in failures:
        print(f"  FAILED {key}: {err}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
