#!/usr/bin/env python3
"""One traced benchmark run, read against the program's own spans.

    python3 tools/span_trace.py --workload <cell> --seed <n> --seconds <s> \\
        [--keep DIR]

Runs the cell as ``bench/run.py --trace 1`` does, in this process and on
the chip it finds (its result line is printed as usual), keeping what
the harness throws away: the engine's tracer and the profiler's trace.
Then prints one line ``span_trace {...}``:

- ``clock``: every ``decode.*`` / ``host.gc`` span of the traced slice
  beside the ``jax.profiler.TraceAnnotation`` it opened, after the one
  offset the harness takes from its ``bench.clock`` mark: the worst
  disagreement of starts and of ends, over the whole slice and over its
  first and last second;
- ``idle``: the device's idle time in the slice, split by the innermost
  span the host was in ("harness loop" outside every span, "<name>
  (self)" for a span's own time);
- ``longest_gaps``: the longest idle gaps and the span they fell in;
- ``setup``: set-up seconds (process start to the window) in
  ``decode.materialize``, in ``xla.compile`` and elsewhere.

With ``--keep DIR`` it also writes ``DIR/<cell>.hostplane.txtpb.gz``,
the profiler's host planes cut to the annotations of those spans and the
clock mark (an XSpace in text form), and ``DIR/<cell>.spans.json``, the
spans on the program's clock: the data ``bench/tests/test_span_clock.py``
reads.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import run  # noqa: E402
import trace_reduce  # noqa: E402

# the spans whose annotations the clock comparison reads
PREFIXES = ("decode.", "host.gc")
IDLE = run.load_module(ROOT / "bench" / "layer_metrics"
                       / "idle_with_work_share.py")


def closed_spans(events, window=None) -> list:
    """Tracer events -> [(name, start, end)] in seconds, lane 0, each
    inside ``window`` when given, in order of their start."""
    lo, hi = window or (float("-inf"), float("inf"))
    out = [(n, s, e) for n, s, e, _ in run.span_list(
        [ev for ev in events if ev.get("tid", 0) == 0])[0]
        if lo <= s and e <= hi]
    return sorted(out, key=lambda x: x[1])


def annotations(pd, names) -> dict:
    """name -> sorted [(start_s, end_s)] of the host-plane events of
    those names, on the profiler's clock."""
    out: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns * 1e-9, ev.end_ns * 1e-9))
    return {k: sorted(v) for k, v in out.items()}


def clock_agreement(pd, mark: float, spans) -> dict:
    """Each span beside its annotation, the n-th span of a name against
    the n-th annotation of that name inside the slice, after the offset
    of the ``bench.clock`` mark.  Returns the number of spans, the
    annotations left unmatched, and the worst |difference| of starts and
    of ends (seconds) over all spans and over the first and last second
    of the slice, with the median signed difference of starts there (a
    drift of one clock against the other moves it)."""
    names = {n for n, _, _ in spans} | {trace_reduce.CLOCK_MARK}
    ann = annotations(pd, names)
    offset = ann[trace_reduce.CLOCK_MARK][0][0] - mark
    lo, hi = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    by_name: dict = {}
    for n, s, e in spans:
        by_name.setdefault(n, []).append((s, e))
    rows, unmatched = [], 0
    for n, mine in by_name.items():
        theirs = [(a - offset, b - offset) for a, b in ann.get(n, [])
                  if lo - 1e-3 <= a - offset and b - offset <= hi + 1e-3]
        unmatched += abs(len(theirs) - len(mine))
        rows += [(s, a - s, b - e) for (s, e), (a, b) in zip(mine, theirs)]

    def worst(sel):
        starts = sorted(r[1] for r in sel)
        return {"start_s": max((abs(r[1]) for r in sel), default=None),
                "end_s": max((abs(r[2]) for r in sel), default=None),
                "median_start_s": starts[len(starts) // 2] if sel else None,
                "spans": len(sel)}
    return {"offset_s": offset, "spans": len(spans), "unmatched": unmatched,
            "all": worst(rows),
            "first_second": worst([r for r in rows if r[0] < lo + 1.0]),
            "last_second": worst([r for r in rows if r[0] > hi - 1.0])}


def idle_by_span(events, trace) -> tuple:
    """Device-idle seconds of the traced window (averaged over devices)
    by the innermost lane-0 span the host was in, and the longest idle
    gaps with theirs."""
    lo, hi = trace["window"]
    segs, stack, t_prev = [], [], lo
    for ev in events:
        if ev.get("tid", 0) != 0 or ev["ph"] not in ("B", "E"):
            continue
        t = ev["ts"] * 1e-6
        label = (f"{stack[-1]} (self)" if stack else "harness loop")
        if t > t_prev:
            segs.append((t_prev, t, label))
            t_prev = t
        if ev["ph"] == "B":
            stack.append(ev["name"])
        elif stack:
            stack.pop()
    segs.append((t_prev, float("inf"),
                 f"{stack[-1]} (self)" if stack else "harness loop"))
    split, gaps_all = {}, []
    devs = list(trace["devices"].values())
    for ops in devs:
        j = 0
        for a, b in IDLE.idle_gaps(ops, lo, hi):
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            k, where = j, {}
            while k < len(segs) and segs[k][0] < b:
                d = min(b, segs[k][1]) - max(a, segs[k][0])
                if d > 0:
                    split[segs[k][2]] = split.get(segs[k][2], 0.0) \
                        + d / len(devs)
                    where[segs[k][2]] = where.get(segs[k][2], 0.0) + d
                k += 1
            gaps_all.append((b - a, a, max(where, key=where.get)
                             if where else "harness loop"))
    longest = [{"seconds": d, "at_s": a - lo, "in": w}
               for d, a, w in sorted(gaps_all, reverse=True)[:5]]
    return dict(sorted(split.items(), key=lambda kv: -kv[1])), longest


def setup_split(events, t_process: float, t_window: float) -> dict:
    """Set-up seconds by span (top-level ``decode.materialize`` and
    ``xla.compile`` time before the window) and the rest."""
    spent = {"decode.materialize": 0.0, "xla.compile": 0.0}
    for n, s, e in closed_spans(events, (t_process, t_window)):
        if n in spent:
            spent[n] += e - s
    total = t_window - t_process
    return {"setup_s": total, **spent, "rest": total - sum(spent.values())}


def hostplane_text(pd, names) -> str:
    """The host planes of ``pd`` cut to the events named in ``names``,
    as an XSpace in text form (what
    ``ProfileData.text_proto_to_serialized_xspace`` reads)."""
    planes = []
    for pid, plane in enumerate(pd.planes, 1):
        if plane.name.startswith("/device:"):
            continue
        meta, lines = {}, []
        for lid, line in enumerate(plane.lines, 1):
            evs = []
            for ev in line.events:
                if ev.name not in names:
                    continue
                mid = meta.setdefault(ev.name, len(meta) + 1)
                evs.append(f"  events {{ metadata_id: {mid} offset_ps: "
                           f"{round(ev.start_ns * 1e3)} duration_ps: "
                           f"{round(ev.duration_ns * 1e3)} }}")
            if evs:
                lines.append(f" lines {{ id: {lid} name: "
                             f"{json.dumps(line.name)} timestamp_ns: 0\n"
                             + "\n".join(evs) + "\n }")
        if lines:
            md = "\n".join(f" event_metadata {{ key: {i} value {{ id: {i} "
                           f"name: {json.dumps(n)} }} }}"
                           for n, i in meta.items())
            planes.append(f"planes {{\n id: {pid}\n name: "
                          f"{json.dumps(plane.name)}\n"
                          + "\n".join(lines) + "\n" + md + "\n}")
    return "\n".join(planes) + "\n"


def record(cell, seed: int, seconds: float, devices=None,
           t_process: float = T_PROCESS) -> dict:
    """Run ``cell`` traced and return what the harness discards: the
    tracer's events, the xplane bytes, the clock mark, the reduced
    trace, the window's open and the result line."""
    import jax
    kept: dict = {}
    build, reduce_xplane, serve = (run.build_engine,
                                   trace_reduce.reduce_xplane,
                                   run.serve_window)

    def build_keeping(*args, **kw):
        kept["tracer"] = kw.get("tracer")
        return build(*args, **kw)

    def reduce_keeping(trace_dir, mark, window):
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        kept.update(xplane=pathlib.Path(path).read_bytes(), mark=mark)
        kept["trace"] = reduce_xplane(trace_dir, mark, window)
        return kept["trace"]

    def serve_keeping(*args, **kw):
        out = serve(*args, **kw)
        kept["t0"] = out[1]
        return out

    run.build_engine = build_keeping
    trace_reduce.reduce_xplane = reduce_keeping
    run.serve_window = serve_keeping
    try:
        kept["result"] = run.run_cell(cell, seed, seconds, True,
                                      t_process=t_process, devices=devices)
    finally:
        run.build_engine, trace_reduce.reduce_xplane, run.serve_window = \
            build, reduce_xplane, serve
    kept["events"] = list(kept.pop("tracer").events)
    kept["pd"] = jax.profiler.ProfileData.from_serialized_xspace(
        kept["xplane"])
    return kept


def summarize(kept, t_process: float = T_PROCESS) -> dict:
    window = tuple(kept["trace"]["window"])
    spans = [s for s in closed_spans(kept["events"], window)
             if s[0].startswith(PREFIXES)]
    split, longest = idle_by_span(kept["events"], kept["trace"])
    return {"clock": clock_agreement(kept["pd"], kept["mark"], spans)
            if spans else None,
            "idle": split, "longest_gaps": longest,
            "setup": setup_split(kept["events"], t_process, kept["t0"])}


def keep(kept, cell_name: str, out: pathlib.Path) -> None:
    window = tuple(kept["trace"]["window"])
    spans = [s for s in closed_spans(kept["events"], window)
             if s[0].startswith(PREFIXES)]
    names = {n for n, _, _ in spans} | {trace_reduce.CLOCK_MARK}
    out.mkdir(parents=True, exist_ok=True)
    with gzip.open(out / f"{cell_name}.hostplane.txtpb.gz", "wt") as f:
        f.write(hostplane_text(kept["pd"], names))
    (out / f"{cell_name}.spans.json").write_text(json.dumps(
        {"cell": cell_name, "mark": kept["mark"], "window": list(window),
         "spans": spans}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=pathlib.Path)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devices = run.find_chips(cell.chips)
    except run.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    run.use_compile_cache()
    kept = record(cell, args.seed, args.seconds, devices)
    print("span_trace " + json.dumps(summarize(kept)), flush=True)
    if args.keep is not None:
        keep(kept, args.workload, args.keep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
