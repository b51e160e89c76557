#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/mixes/<traffic>.json``, read by ``bench/traffic/<generator>.py``),
with its output limits in ``bench/limits/<workload>.json``.  Per-layer
metrics are read by ``bench/layer_metrics/<metric>.py``.  Everything is
found by name, so a new configuration of any family is new files plus
entries.

Whatever depends on the model's family comes from the configuration
file, which names it:

- ``"family"``: the directory ``bench/families/<family>/``, loaded as a
  package of its own (:func:`load_family`), whose modules may import
  each other relatively.  The dense GQA family is ``dense``.  It holds:

  - ``arch.py``: ``from_config(conf)``, the arch object the other two
    take, which exposes at least ``vocab``, ``n_layers`` and
    ``split_layer``; and ``program_fields(arch)``, the program
    ``ModelConfig``'s fields that the file fixes, with their values.
  - ``counts.py``: ``prefill_flops(arch, prompt_len)``,
    ``decode_token_flops(arch, ctx_len)``, ``decode_attn_work(arch,
    ctx_len, code_bytes) -> (flops, bytes)`` and ``least_time(flops,
    nbytes, peaks) -> (seconds, bound)``; per-layer readers find them
    as ``run.counts``.
  - ``reference.py``: ``init_params(arch, key)``, ``agent_quantized(arch,
    params, b_hat)`` and ``logits_rows(arch, params, tokens, n_prompt, *,
    n_rows, b_kv, lowp=False)``, the float32 logits [n_rows, vocab] that
    score the served tokens.  It imports nothing of the program.
- ``"program_arch"`` and ``"program_overrides"``: the program's
  ``ModelConfig``, ``repro.configs.<program_arch>.FULL`` with the
  overrides, refused where it differs from ``program_fields``.  The
  program's own registry (``repro.models.registry.build_model``) picks
  the model class for it; ``bench/faults.py`` plants
  ``state_unchanged`` on that class's ``decode_step_q``.

The run: weights from the seed on the device, the engine built and its
executables warmed with the cell's own shapes (set-up, reported as
``setup_s``), then a window of ``--seconds`` in which the traffic is
served through ``DecodeEngine.submit``/``step``, each token stamped with
the host clock as it reaches the host.  Requests due in the window and
still in flight at its close are drained after it.  Then the program's
state is freed and a sample of the served tokens is scored against the
configuration's plain reference: ``correct`` holds when every
number compared is within its limit.  ``--trace 1`` runs the window under
the JAX profiler and reports the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``, each number compared beside
its limit.  The same checks end standard error.  Off a TPU, or with
fewer chips than the cell asks for, the run prints no result and exits
with 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

import trace_reduce  # noqa: E402
from peaks import peaks_for  # noqa: E402


class NoChip(RuntimeError):
    """The process holds no chip of the kind and number the cell needs."""


# ---------------------------------------------------------------------------
# the cell, from its files
# ---------------------------------------------------------------------------

def _load_json(path: pathlib.Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY_MODULES = ("arch", "counts", "reference")
_FAMILIES: dict = {}


def load_family(path: pathlib.Path) -> types.ModuleType:
    """The model family in the directory ``path``: a package of its own,
    under a name of its own, whose modules :data:`FAMILY_MODULES` are
    executed once per process."""
    path = pathlib.Path(path).resolve()
    if path not in _FAMILIES:
        name = f"bench_family_{len(_FAMILIES)}_" + path.name \
            .replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_loader(name, None, is_package=True)
        pkg = importlib.util.module_from_spec(spec)
        pkg.__path__ = [str(path)]
        sys.modules[name] = pkg
        for part in FAMILY_MODULES:
            importlib.import_module(f"{name}.{part}")
        _FAMILIES[path] = pkg
    return _FAMILIES[path]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list
    bench: pathlib.Path
    family: types.ModuleType     # the package the configuration names

    @property
    def arch(self):
        return self.family.arch.from_config(self.config)


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Everything the cell ``name`` needs, found by name under ``root``."""
    spec = _load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    bench = root / "bench"
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    config = _load_json(root / conf["file"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=_load_json(bench / "mixes" / f"{w['traffic']}.json"),
                limits=_load_json(bench / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, bench=bench,
                family=load_family(bench / "families" / config["family"]))


def find_chips(chips: int):
    """The devices of the run; raises NoChip off a TPU, with fewer chips
    than ``chips``, or on a chip with no entry in the peak table."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    try:
        peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return devs


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

# the precisions a configuration may state, by their width
_BITS = {"float64": 64, "float32": 32, "bfloat16": 16, "float16": 16}


def program_config(cell: Cell):
    """The program's ModelConfig for the cell's configuration file,
    checked against the fields its family's arch module says the file
    fixes (``program_fields``): the file says what runs.  The program may hold
    and compute at the precision the file states (``torch_dtype``) or
    wider, never narrower."""
    conf = cell.config
    base = importlib.import_module(f"repro.configs.{conf['program_arch']}")
    cfg = dataclasses.replace(base.FULL, **conf.get("program_overrides", {}))
    want = cell.family.arch.program_fields(cell.arch)
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    stated = conf["model"]["torch_dtype"]
    for k in ("param_dtype", "dtype"):
        if _BITS.get(str(getattr(cfg, k)), 0) < _BITS[stated]:
            bad[k] = (getattr(cfg, k), f"{stated} or wider")
    if bad:
        raise ValueError(f"program config differs from {conf['name']}'s "
                         f"file (program, file): {bad}")
    return cfg


def build_model(cfg, seed: int):
    """(model, weights): the model the program's registry builds for
    ``cfg``, and its weights from ``seed`` in one jitted call on the
    device."""
    import jax
    from repro.models import registry
    model = registry.build_model(cfg)
    return model, jax.jit(model.init)(jax.random.PRNGKey(seed))


def build_engine(cell: Cell, model, params, tracer=None,
                 compile_cache=None):
    """The DecodeEngine as ``launch/serve.py --decode`` builds it
    (continuous admission), with one QoS class pinned at the mix's
    operating point.  The system parameters and λ statistics feed only
    the codesign and the modeled clock, which a pinned point and a
    wall-clock benchmark do not use, so λ is given rather than fitted."""
    from repro.core.cost_model import SystemParams
    from repro.runtime.decode_engine import DecodeEngine
    from repro.runtime.serve_engine import QosClass
    mix = cell.mix
    eng = DecodeEngine(model, params,
                       SystemParams(n_flop_agent=1.0, n_flop_server=1.0),
                       classes=[QosClass(mix["qos"], t0=1.0, e0=1.0)],
                       max_batch=mix["max_batch"],
                       max_new_tokens=mix["output_len"][1], auto=False,
                       lam=1.0, lam_kv=1.0, tracer=tracer,
                       compile_cache=compile_cache)
    eng.set_operating_point(mix["qos"], mix["b_hat"], mix["b_kv"])
    return eng


# ---------------------------------------------------------------------------
# one window of traffic
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    rid: int
    due: float                 # monotonic seconds
    submitted: float
    prompt: np.ndarray
    max_new: int
    toks: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.toks) >= self.max_new


def warm_up(eng, cell: Cell, seed: int) -> None:
    """Serve, before the window, two requests of each (prompt bucket,
    cache bucket) pair that the mix's length ranges reach: every
    executable and every host path the window takes runs once, and
    nothing else is compiled."""
    from repro.kernels.bucketing import seq_bucket
    mix = cell.mix
    rng = np.random.default_rng([seed, 2])
    classes = {}
    for p in range(mix["prompt_len"][0], mix["prompt_len"][1] + 1):
        for o in range(mix["output_len"][0], mix["output_len"][1] + 1):
            key = (seq_bucket(p, eng.seq_bucket_base),
                   seq_bucket(p + o, eng.seq_bucket_base))
            best = classes.get(key, (0, 1 << 30))
            classes[key] = (max(best[0], p), min(best[1], o))
    for p, o in classes.values():
        for _ in range(2):
            eng.submit(rng.integers(0, cell.arch.vocab, p, dtype=np.int32),
                       mix["qos"], max_new_tokens=o,
                       on_token=lambda *a: None)
        while eng.pending or eng.in_flight:
            eng.step()


def serve_window(eng, cell: Cell, seed: int, seconds: float,
                 profile_dir=None):
    """Drive the engine with the mix's traffic for ``seconds`` and drain
    what was due in the window.  Returns (requests, t0, t_close,
    traced), ``traced`` None or (mark, start, end): the profiler ran
    from ``start`` to ``end``, and ``mark`` is the monotonic time of its
    clock annotation.

    With ``profile_dir``, the window is cut short: the profiler starts
    after the mix's ``trace_lead_s`` (the traffic settles first), or
    after the engine call running then, and the window closes when it
    has recorded ``trace_s``.  A device trace holds every operation,
    hundreds of thousands a second, and reading it has to fit a run's
    time."""
    import jax
    mix = cell.mix
    gen = load_module(cell.bench / "traffic" / f"{mix['generator']}.py")
    source = gen.Source(mix, seed, seconds, cell.arch.vocab)
    reqs: dict = {}

    def on_token(rid, tok, _modeled_t):
        now = time.monotonic()
        r = reqs[rid]
        r.toks.append(int(tok))
        r.times.append(now)
        if r.done:
            source.finished(r.due - t0, now - t0)

    def submit_due(now):
        for toks, max_new, due in source.poll(now - t0):
            rid = eng.submit(toks, mix["qos"], max_new_tokens=max_new,
                             on_token=on_token)
            reqs[rid] = Req(rid, t0 + due, time.monotonic(), toks, max_new)

    lead = min(mix["trace_lead_s"], seconds / 2)
    trace_s = min(mix["trace_s"], seconds - lead)
    traced = None
    t0 = time.monotonic()
    t_end = t0 + (lead + trace_s if profile_dir is not None else seconds)
    while True:
        now = time.monotonic()
        if profile_dir is not None and traced is None and now >= t0 + lead:
            jax.profiler.start_trace(profile_dir)
            mark = time.monotonic()
            with jax.profiler.TraceAnnotation(trace_reduce.CLOCK_MARK):
                pass
            traced = (mark, time.monotonic())
            t_end = traced[1] + trace_s
            continue
        if now >= t_end:
            break
        submit_due(now)
        if eng.pending or eng.in_flight:
            eng.step()
        else:
            nxt = source.next_due()
            wake = t_end if nxt is None else min(t_end, t0 + nxt)
            time.sleep(max(0.0, wake - time.monotonic()))
    t_close = time.monotonic()
    if traced is not None:
        jax.profiler.stop_trace()
        traced = (traced[0], traced[1], t_close)
    submit_due(t_end - 1e-9)
    deadline = time.monotonic() + 60.0
    while (eng.pending or eng.in_flight) and time.monotonic() < deadline:
        eng.step()
    return list(reqs.values()), t0, t_close, traced


def p95(values):
    return float(np.percentile(np.asarray(values, np.float64), 95)) \
        if len(values) else None


def end_to_end(reqs, t0: float, t_close: float) -> dict:
    """The user-visible numbers of a window (before ``setup_s``).

    ``tokens_per_s`` is every token that reached the host from the
    window's open to its close, over that time.  The window closes when
    the engine call running at its planned end returns, so that call's
    tokens count with its time: a chunk emits its tokens together, and
    cutting the count at the planned end would add or drop a whole
    chunk's tokens against the same time."""
    fin = [r for r in reqs if r.done]
    ttft = [r.times[0] - r.due for r in fin]
    lat = [r.times[-1] - r.due for r in fin]
    tpot = [(r.times[-1] - r.times[0]) / (len(r.times) - 1)
            for r in fin if len(r.times) > 1]
    emitted = sum(1 for r in reqs for t in r.times if t0 <= t <= t_close)
    return {"ttft_p95_ms": ("ms", 1e3 * p95(ttft) if ttft else None),
            "latency_p95_ms": ("ms", 1e3 * p95(lat) if lat else None),
            "tpot_p95_ms": ("ms", 1e3 * p95(tpot) if tpot else None),
            "tokens_per_s": ("tokens/s", emitted / (t_close - t0))}


# ---------------------------------------------------------------------------
# is the output right
# ---------------------------------------------------------------------------

def check_sample(reqs, cell: Cell, seed: int) -> list:
    """The finished requests the reference scores: the one with the most
    served tokens (then the longest prompt), and others drawn from the
    seed until ``check_tokens`` served tokens are covered."""
    fin = [r for r in reqs if r.done]
    if not fin:
        return []
    first = max(fin, key=lambda r: (len(r.toks), len(r.prompt)))
    rest = [r for r in fin if r is not first]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    sample, n = [first], len(first.toks)
    for i in order:
        if n >= cell.mix["check_tokens"]:
            break
        sample.append(rest[i])
        n += len(rest[i].toks)
    return sample


def reference_gaps(cell: Cell, seed: int, sample, control=False):
    """Per sampled request, the gap by which each served token's logit
    lies below the float32 reference's best at that position.  With
    ``control``, also the gaps of the tokens that the reference with
    fp8-rounded matmul operands puts first, read in the float32
    reference: the lower-precision control."""
    import jax
    import jax.numpy as jnp
    ref, a, mix = cell.family.reference, cell.arch, cell.mix
    n_rows, s_len = mix["output_len"][1], mix["reference_len"]
    p = jax.jit(lambda k: ref.agent_quantized(
        a, ref.init_params(a, k), mix["b_hat"]))(jax.random.PRNGKey(seed))
    served, lowp = [], []
    for r in sample:
        n_p, n = len(r.prompt), len(r.toks)
        toks = np.zeros((s_len,), np.int32)
        toks[:n_p] = r.prompt
        toks[n_p:n_p + n - 1] = r.toks[:-1]
        args = (a, p, jnp.asarray(toks), jnp.asarray(n_p, jnp.int32))
        lg = np.asarray(ref.logits_rows(*args, n_rows=n_rows,
                                        b_kv=mix["b_kv"]))[:n]
        best = lg.max(axis=-1)
        served.append(best - lg[np.arange(n), np.asarray(r.toks)])
        if control:
            top = np.asarray(ref.logits_rows(
                *args, n_rows=n_rows, b_kv=mix["b_kv"], lowp=True))[:n]
            lowp.append(best - lg[np.arange(n), top.argmax(axis=-1)])
    del p
    return served, lowp


def output_checks(cell: Cell, reqs, seed: int) -> dict:
    """Each number compared, beside its limit."""
    gaps, _ = reference_gaps(cell, seed, check_sample(reqs, cell, seed))
    return score(cell, reqs, gaps)


def score(cell: Cell, reqs, gaps) -> dict:
    """The checks of a window's requests whose sampled tokens read
    ``gaps`` (one array per sampled request) against the reference."""
    widest = float(max(g.max() for g in gaps)) if gaps else float("inf")
    return {
        "unfinished": {"value": sum(1 for r in reqs if not r.done),
                       "limit": 0},
        "max_logit_gap": {"value": widest,
                          "limit": cell.limits["max_logit_gap"]["limit"]},
        "served_tokens_checked": {"value": int(sum(len(g) for g in gaps)),
                                  "min": int(cell.mix["check_tokens"])},
    }


def passed(checks: dict) -> bool:
    ok = True
    for c in checks.values():
        if "limit" in c:
            ok &= c["value"] <= c["limit"]
        if "min" in c:
            ok &= c["value"] >= c["min"]
    return bool(ok)


# ---------------------------------------------------------------------------
# per-layer metrics from spans and the profiler trace
# ---------------------------------------------------------------------------

def span_list(events) -> tuple:
    """Tracer events -> (closed spans [(name, start, end, args)],
    instants [(name, t, args)]), times in monotonic seconds."""
    spans, instants, open_ = [], [], {}
    for ev in events:
        key = (ev["name"], ev.get("tid", 0))
        if ev["ph"] == "B":
            open_.setdefault(key, []).append(ev)
        elif ev["ph"] == "E":
            b = open_[key].pop()
            spans.append((ev["name"], b["ts"] * 1e-6, ev["ts"] * 1e-6,
                          b.get("args", {})))
        elif ev["ph"] == "i":
            instants.append((ev["name"], ev["ts"] * 1e-6, ev.get("args", {})))
    return spans, instants


def layer_context(cell: Cell, reqs, spans, instants, window, trace,
                  peaks) -> types.SimpleNamespace:
    """What every per-layer reader reads."""
    lo, hi = window
    return types.SimpleNamespace(
        spans=[s for s in spans if lo <= s[1] and s[2] <= hi],
        instants=[i for i in instants if lo <= i[1] <= hi],
        requests=reqs, window=window, trace=trace, arch=cell.arch,
        counts=cell.family.counts, max_batch=cell.mix["max_batch"],
        peaks=peaks, code_bytes=1 if cell.mix["b_kv"] < 16 else 4)


def read_layer_metrics(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(cell.bench / "layer_metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(ctx) -> dict:
    """The device ops that took most time (self time: less the ops nested
    in them), and the device's idle time by what the host was doing,
    over the traced window: each idle gap split among the engine's
    prefill and chunk spans and the time outside them."""
    lo, hi = ctx.window
    ops = [o for dev in ctx.trace["devices"].values() for o in dev]
    phases = [(s[1], s[2], s[0]) for s in ctx.spans
              if s[0] in ("decode.prefill", "decode.chunk")]
    where = {}
    for name, span_set in (("prefill", "decode.prefill"),
                           ("decode", "decode.chunk")):
        for o in trace_reduce.ops_within(
                ops, [(a, b) for a, b, n in phases if n == span_set]):
            where[id(o)] = name
    per_op = {}
    for dev in ctx.trace["devices"].values():
        for o, own in trace_reduce.self_seconds(dev):
            key = f"{where.get(id(o), 'other')}:{o[0]}"
            per_op[key] = per_op.get(key, 0.0) + own
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    ivs = sorted((max(o[1], lo), min(o[1] + o[2], hi)) for o in ops)
    gaps, cur = [], lo
    for s, e in ivs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    idle = {}

    def add(label, t):
        tot, cnt, longest = idle.get(label, (0.0, 0, 0.0))
        idle[label] = (tot + t, cnt + 1, max(longest, t))
    phases.sort()
    j = 0
    for s, e in gaps:
        while j < len(phases) and phases[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(phases) and phases[k][0] < e:
            a, b, name = phases[k]
            if a > cur:
                add("host outside engine calls", a - cur)
            if min(b, e) > max(a, cur):
                add(name, min(b, e) - max(a, cur))
            cur = max(cur, min(b, e))
            k += 1
        if e > cur:
            add("host outside engine calls", e - cur)
    idle_rows = sorted(idle.items(), key=lambda kv: -kv[1][0])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[f"{k} ({c} gaps, longest {m:.6f} s)", t]
                          for k, (t, c, m) in idle_rows]}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, devices=None) -> dict:
    """One run of ``cell``: prints the result line (see module doc) and
    the checks, and returns the result.  ``devices`` are the chips
    :func:`find_chips` returned; None runs on whatever JAX has (the CPU
    tests)."""
    import jax

    from repro.obs import Tracer
    compiles = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.monotonic())
    jax.monitoring.register_event_duration_secs_listener(on_event)

    model, params = build_model(program_config(cell), seed)
    tracer = Tracer() if trace else None
    eng = build_engine(cell, model, params, tracer=tracer)
    warm_up(eng, cell, seed)
    n_compiled = eng.report().compile_misses
    profile_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = time.monotonic() - t_process
    try:
        reqs, t0, t_close, traced = serve_window(eng, cell, seed, seconds,
                                                 profile_dir)
        in_window = sum(1 for t in compiles if t0 <= t <= t_close)
        engine_compiles = eng.report().compile_misses - n_compiled
        devs = devices or jax.devices()
        stats = [d.memory_stats() or {} for d in devs[:max(cell.chips, 1)]]
        peak = max(m.get("peak_bytes_in_use", 0) for m in stats)
        # executables' temporaries are reserved apart from the buffers
        # that peak_bytes_in_use counts
        reserved = max(m.get("peak_bytes_reserved", 0) for m in stats)
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": int(peak)}
        if trace:
            spans, instants = span_list(tracer.events)
            reduced = trace_reduce.reduce_xplane(profile_dir, traced[0],
                                                 traced[1:])
    finally:
        if profile_dir is not None:
            shutil.rmtree(profile_dir, ignore_errors=True)
    del eng, model, params
    gc.collect()

    lateness = [r.submitted - r.due for r in reqs]
    info = {"operating_point": {k: cell.mix[k] for k in
                                ("qos", "b_hat", "b_kv", "max_batch")},
            "compiles_in_window": in_window,
            "engine_compiles_in_window": engine_compiles,
            "generator_lateness_ms": {"p95": 1e3 * p95(lateness),
                                      "max": 1e3 * max(lateness)}
            if lateness else None,
            "peak_bytes_in_use": device["memory_peak_bytes"],
            "peak_bytes_reserved": reserved,
            "window_s": t_close - t0}
    print("info " + json.dumps(info), flush=True)

    checks = output_checks(cell, reqs, seed)
    result = {"correct": passed(checks), "attempted": len(reqs),
              "failed": checks["unfinished"]["value"]}
    if trace:
        peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" \
            else None
        ctx = layer_context(cell, reqs, spans, instants, traced[1:],
                            reduced, peaks)
        result["metrics"] = read_layer_metrics(cell, ctx)
        device["busy_s"] = trace_reduce.busy_seconds(reduced)
        device["window_s"] = traced[2] - traced[1]
        result["device"] = device
        result["breakdown"] = breakdown(ctx)
    else:
        e2e = end_to_end(reqs, t0, t_close)
        e2e["setup_s"] = ("s", setup_s)
        wanted = {m["name"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (u, v) in e2e.items()
                             if k in wanted and v is not None}
        result["device"] = device
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    for name, c in checks.items():
        bound = f"limit {c['limit']}" if "limit" in c else f"min {c['min']}"
        print(f"check {name}: {c['value']} ({bound})", file=sys.stderr,
              flush=True)
    return result


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed place in
    the checkout (or ``$JAX_COMPILATION_CACHE_DIR``), keeping every
    executable however small or quick to build, so that a cell's second
    run in a checkout compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devices = find_chips(cell.chips)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    use_compile_cache()
    run_cell(cell, args.seed, args.seconds, bool(args.trace),
             t_process=T_PROCESS, devices=devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
