"""The harness end to end at a smoke size on the CPU: its result line, its
refusal to run off a TPU, cells and metrics found by name, and a broken
timed path caught by the output check.

Every run here builds a tiny copy of the benchmark in a temporary
directory: the real files plus a new configuration, mix, limit and
per-layer metric, each a new file with an entry in ``BENCHMARK.json``,
so the test also shows that adding them edits no existing file.  A
second copy adds a model family the benchmark does not have, a decoder
with routed experts, with its own arch module, counts and reference
(``tests/families/tiny_moe/``), and checks that nothing copied was
edited.  The
tiny cells' limit is their own: on the CPU the program and the reference
are both float32 at full precision and the widest gap reads 0.0, so
1e-3 sits far above sound runs and far below every planted fault.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import faults  # noqa: E402
import run  # noqa: E402

SEED = 2 ** 31 + 11
TINY_CONFIG = {
    "name": "tiny", "source": "test", "program_arch": "qwen2_0_5b",
    "family": "dense",
    "program_overrides": {"n_layers": 3, "d_model": 64, "n_heads": 4,
                          "n_kv_heads": 2, "head_dim": 16, "d_ff": 160,
                          "vocab_size": 512, "split_layer": 1},
    "model": {"hidden_size": 64, "intermediate_size": 160,
              "num_hidden_layers": 3, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
              "tie_word_embeddings": True, "rms_norm_eps": 1e-6,
              "rope_theta": 1e6, "torch_dtype": "float32"},
    "arch": {"norm": "rmsnorm", "qkv_bias": True}, "split_layer": 1}
NEW_METRIC = '''
def read(run):
    return len(run.requests) or None
'''


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((BENCH / "mixes" / "embodied.json").read_text())
    mix.update(rate_rps=6, prompt_len=[20, 40], output_len=[3, 12],
               max_batch=4, check_tokens=30, reference_len=64)
    files = {"configs/tiny.json": TINY_CONFIG, "mixes/tinymix.json": mix,
             "limits/tiny.tinymix.json": {"max_logit_gap": {"limit": 1e-3}}}
    for rel, obj in files.items():
        path = root / "bench" / rel
        assert not path.exists()
        path.write_text(json.dumps(obj))
    (root / "bench/layer_metrics/requests_seen.py").write_text(NEW_METRIC)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.tinymix", "config": "tiny",
                              "traffic": "tinymix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.tinymix")
    spec["per_layer"].append({"name": "requests_seen", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "tokens_per_s",
                              "workloads": ["tiny.tinymix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def model_class(cell):
    """The class of the model the harness builds for ``cell``."""
    from repro.models import registry
    return type(registry.build_model(run.program_config(cell)))


def run_tiny(root, capsys, trace=False, **mix):
    cell = run.load_cell("tiny.tinymix", root=root)
    cell.mix.update(mix)
    run.run_cell(cell, SEED, 3.0, trace, t_process=time.monotonic())
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_run_prints_one_result_line(tiny_root, capsys):
    res, err = run_tiny(tiny_root, capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 18          # 6/s over 3 s, every seed
    assert set(res["metrics"]) == {"ttft_p95_ms", "latency_p95_ms",
                                   "tpot_p95_ms", "tokens_per_s", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["checks"]["max_logit_gap"]["value"] == 0.0
    assert res["checks"]["served_tokens_checked"]["value"] >= 30
    assert err.strip().splitlines()[-1].startswith("check ")


def test_traced_run_reads_layer_metrics_found_by_name(tiny_root, capsys):
    res, _ = run_tiny(tiny_root, capsys, trace=True)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]
    assert res["correct"] is True
    # the new metric file is read without any edit of the harness
    assert res["metrics"]["requests_seen"]["value"] == res["attempted"]
    for name in ("queue_wait_p95_ms", "batch_occupancy", "prefill_ms",
                 "decode_step_ms"):
        assert res["metrics"][name]["value"] > 0
    # no device ops on the CPU: the device readers find nothing to read
    for name in ("prefill_mfu", "decode_mfu", "decode_attn_roofline",
                 "device_idle_share"):
        assert name not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])


def run_stalled(tiny_root, capsys, monkeypatch, until):
    """A traced tiny run whose window's first engine call stalls until
    ``until`` seconds after the window opens.  The mix profiles 1.5 s
    from 1.5 s into the 3 s window."""
    warm, serve = run.warm_up, run.serve_window
    opened = {}

    def serve_marking(*args, **kw):
        opened["t"] = time.monotonic()
        return serve(*args, **kw)

    def warm_then_stall(eng, cell, seed):
        warm(eng, cell, seed)
        step = eng.step

        def stalled():
            eng.step = step
            time.sleep(max(0.0, opened["t"] + until - time.monotonic()))
            return step()
        eng.step = stalled
    monkeypatch.setattr(run, "warm_up", warm_then_stall)
    monkeypatch.setattr(run, "serve_window", serve_marking)
    res, _ = run_tiny(tiny_root, capsys, trace=True)
    assert res["correct"] is True
    assert res["device"]["window_s"] >= 1.5
    assert res["metrics"]["decode_step_ms"]["value"] > 0


def test_traced_run_outlasts_a_stall_past_its_slice(tiny_root, capsys,
                                                   monkeypatch):
    """The window's first engine call stalls from before the profiler is
    due to start until after the planned slice would have ended: the
    run traces a whole slice after the stall instead of failing."""
    run_stalled(tiny_root, capsys, monkeypatch, until=4.0)


def test_traced_run_outlasts_a_stall_into_its_slice(tiny_root, capsys,
                                                   monkeypatch):
    """The stall ends just before the planned slice would have ended:
    the slice still records its whole length after the stall, not the
    moments left of the plan."""
    run_stalled(tiny_root, capsys, monkeypatch, until=2.8)


def test_tokens_per_s_counts_the_last_call_with_its_time():
    """A chunk emits its tokens together.  The window closes when the
    call running at its planned end returns: that call's tokens count,
    over the time to its return, and the drain's tokens do not."""
    def req(times):
        r = run.Req(0, 0.0, 0.0, None, len(times))
        r.toks, r.times = [1] * len(times), list(times)
        return r
    # planned end 10.0; the last call returns at 10.5 with 4 tokens,
    # the drain emits 2 more at 11.0
    reqs = [req([1.0, 2.0, 3.0]), req([4.0] + [10.5] * 4 + [11.0] * 2)]
    got = run.end_to_end(reqs, 0.0, 10.5)
    assert got["tokens_per_s"] == ("tokens/s", 8 / 10.5)


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_is_not_correct(tiny_root, capsys, fault):
    undo = faults.plant(fault, model_class(
        run.load_cell("tiny.tinymix", root=tiny_root)))
    try:
        # a load that keeps every slot busy, so the upper half serves too
        res, _ = run_tiny(tiny_root, capsys, rate_rps=200)
    finally:
        undo()
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]


def test_cli_refuses_a_backend_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen2-0.5b.embodied", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_control_comes_out_not_correct(tiny_root):
    """The lower-precision control (fp8 matmul operands in the
    reference, put in the program's place) reads past the limit that
    the program's own output stays under."""
    import control
    from repro.runtime.fastpath import CompiledForwardCache
    cell = run.load_cell("tiny.tinymix", root=tiny_root)
    cfg = run.program_config(cell)
    limit = cell.limits["max_logit_gap"]["limit"]
    row = control.read_seed(cell, cfg, SEED, 2.0, CompiledForwardCache(),
                            True)
    assert row["program_gap"] <= limit < row["control_gap"]
    assert row["program_correct"] is True
    assert row["control_correct"] is False


# ---------------------------------------------------------------------------
# a second model family, added as new files only
# ---------------------------------------------------------------------------

FAMILY = BENCH / "tests" / "families"
MOE_CONFIG = {
    "name": "tiny-moe", "source": "test",
    "program_arch": "qwen3_moe_235b_a22b",
    "program_overrides": {"n_layers": 3, "d_model": 64, "n_heads": 4,
                          "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
                          "vocab_size": 512, "n_experts": 4,
                          "experts_per_token": 2, "moe_d_ff": 96,
                          "fsdp": False, "split_layer": 1},
    "family": "tiny_moe",
    "model": {"hidden_size": 64, "num_hidden_layers": 3,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_experts": 4, "num_experts_per_tok": 2,
              "moe_intermediate_size": 96, "norm_topk_prob": True,
              "vocab_size": 512, "tie_word_embeddings": False,
              "rms_norm_eps": 1e-6, "rope_theta": 1e6,
              "torch_dtype": "float32"},
    "arch": {"qkv_bias": False}, "split_layer": 1}
MOE_CELL = "tiny-moe.tinymix"


def _tree(root):
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """A copy of the benchmark with a routed-expert family added: its arch
    module, counts and reference, a configuration, a mix and a limit, all
    new files, and new entries in ``BENCHMARK.json``.  Returns the root
    and the copied tree's files as they were before anything was added."""
    root = tmp_path_factory.mktemp("bench_family")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _tree(root)
    mix = json.loads((BENCH / "mixes" / "embodied.json").read_text())
    mix.update(rate_rps=6, prompt_len=[20, 40], output_len=[3, 12],
               max_batch=4, check_tokens=30, reference_len=64)
    files = {"configs/tiny-moe.json": json.dumps(MOE_CONFIG),
             "mixes/tinymix.json": json.dumps(mix),
             "limits/tiny-moe.tinymix.json":
                 json.dumps({"max_logit_gap": {"limit": 1e-3}})}
    for part in run.FAMILY_MODULES:
        files[f"families/tiny_moe/{part}.py"] = \
            (FAMILY / "tiny_moe" / f"{part}.py").read_text()
    (root / "bench" / "families" / "tiny_moe").mkdir()
    for rel, text in files.items():
        path = root / "bench" / rel
        assert not path.exists()
        path.write_text(text)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-moe", "source": "test",
                            "file": "bench/configs/tiny-moe.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": MOE_CELL, "config": "tiny-moe",
                              "traffic": "tinymix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(MOE_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, before


def run_moe(root, capsys, trace=False, devices=None, **mix):
    cell = run.load_cell(MOE_CELL, root=root)
    cell.mix.update(mix)
    run.run_cell(cell, SEED, 3.0, trace, t_process=time.monotonic(),
                 devices=devices)
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1])


def test_new_family_modules_resolve_from_its_configuration(moe_root):
    root, _ = moe_root
    cell = run.load_cell(MOE_CELL, root=root)
    for part in run.FAMILY_MODULES:
        assert pathlib.Path(getattr(cell.family, part).__file__) == \
            root / "bench" / "families" / "tiny_moe" / f"{part}.py"
    assert type(cell.arch).__name__ == "MoeArch"
    assert (cell.arch.n_experts, cell.arch.experts_per_token) == (4, 2)
    assert model_class(cell).__name__ == "DecoderLM"
    # the dense family's cells still name the dense modules
    dense = run.load_cell("qwen2-0.5b.embodied", root=root)
    assert pathlib.Path(dense.family.counts.__file__) == \
        root / "bench" / "families" / "dense" / "counts.py"


def test_new_family_program_config_takes_its_experts(moe_root):
    root, _ = moe_root
    cell = run.load_cell(MOE_CELL, root=root)
    cfg = run.program_config(cell)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.moe_d_ff) == (4, 2, 96)
    # the fields come from the family's program_fields: a program with
    # other experts than the file states is refused
    cell.config = {**cell.config, "program_overrides": {
        **cell.config["program_overrides"], "experts_per_token": 3}}
    with pytest.raises(ValueError, match="experts_per_token"):
        run.program_config(cell)


def test_new_family_reference_is_the_programs_model(moe_root):
    """The family's reference draws the program's weights and gives its
    logits at full precision on the CPU (the tolerance of
    ``test_reference.py``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    root, _ = moe_root
    cell = run.load_cell(MOE_CELL, root=root)
    model, prog = run.build_model(run.program_config(cell), 7)
    ref = cell.family.reference
    p = ref.init_params(cell.arch, jax.random.PRNGKey(7))
    ffn = prog["layers"]["ffn"]
    for mine, theirs in (("router", "router"), ("e_gate", "wi_gate"),
                         ("e_up", "wi_up"), ("e_down", "wo")):
        np.testing.assert_allclose(np.asarray(p[mine]),
                                   np.asarray(ffn[theirs]), rtol=1e-6,
                                   atol=1e-7, err_msg=mine)
    toks = jax.random.randint(jax.random.PRNGKey(8), (48,), 0, 512)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(prog, {"tokens": toks[None]})
    got = ref.logits_rows(cell.arch, p, toks, jnp.asarray(48, jnp.int32),
                          n_rows=1, b_kv=8)
    scale = float(jnp.max(jnp.abs(want[0, -1])))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0, -1]),
                               atol=2e-5 * scale, rtol=0)


def test_new_family_run_is_correct(moe_root, capsys):
    res = run_moe(moe_root[0], capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["max_logit_gap"]["value"] == 0.0
    assert res["checks"]["served_tokens_checked"]["value"] >= 30


class _Chip:
    """What the harness reads of a TPU v5e device, for a traced run on
    the CPU whose device trace the test makes."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    @staticmethod
    def memory_stats():
        return {}


def test_new_family_traced_run_reads_its_counts(moe_root, capsys,
                                                monkeypatch):
    """A traced run whose device trace holds one op inside every prefill
    and chunk span (the chunk's op a kernel): ``decode_mfu`` and the
    kernel roofline come from the family's counts."""
    import trace_reduce
    kept = {}
    build, read = run.build_engine, run.read_layer_metrics

    def build_keeping(*args, **kw):
        kept["tracer"] = kw.get("tracer")
        return build(*args, **kw)

    def device_ops(trace_dir, mark, window):
        spans, _ = run.span_list(kept["tracer"].events)
        ops = [(name, s, e - s, name == "decode.chunk")
               for name, s, e, _ in spans
               if name in ("decode.prefill", "decode.chunk")]
        return {"window": list(window), "devices": {"/device:TPU:0": ops}}

    def read_keeping(cell, ctx):
        kept["ctx"] = ctx
        return read(cell, ctx)
    monkeypatch.setattr(run, "build_engine", build_keeping)
    monkeypatch.setattr(trace_reduce, "reduce_xplane", device_ops)
    monkeypatch.setattr(run, "read_layer_metrics", read_keeping)
    res = run_moe(moe_root[0], capsys, trace=True, devices=[_Chip()])
    assert res["correct"] is True
    ctx = kept["ctx"]
    assert pathlib.Path(ctx.counts.__file__) == \
        moe_root[0] / "bench" / "families" / "tiny_moe" / "counts.py"
    chunks = [(s, e) for name, s, e, _ in ctx.spans if name == "decode.chunk"]
    dev_s = trace_reduce.device_seconds_within(ctx.trace, chunks)
    flops = sum(ctx.counts.decode_token_flops(ctx.arch, n)
                for _, n in trace_reduce.decoded_tokens(ctx))
    assert flops > 0
    assert res["metrics"]["decode_mfu"]["value"] == pytest.approx(
        100.0 * flops / dev_s / ctx.peaks["flops_bf16"])
    for name in ("prefill_mfu", "decode_attn_roofline"):
        assert res["metrics"][name]["value"] > 0


def test_new_family_state_unchanged_is_not_correct(moe_root, capsys):
    cell = run.load_cell(MOE_CELL, root=moe_root[0])
    undo = faults.plant("state_unchanged", model_class(cell))
    try:
        res = run_moe(moe_root[0], capsys, rate_rps=200)
    finally:
        undo()
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]


def test_new_family_is_only_added_files(moe_root):
    """After the family's runs, every file of the copied benchmark but
    ``BENCHMARK.json`` is as copied, and ``BENCHMARK.json`` differs only
    by the new entries."""
    root, before = moe_root
    after = _tree(root)
    spec = json.loads(after[pathlib.Path("BENCHMARK.json")])
    old = json.loads(before[pathlib.Path("BENCHMARK.json")])
    for rel, data in before.items():
        if rel.name != "BENCHMARK.json":
            assert after[rel] == data, rel
    assert not [rel for rel in after if rel not in before
                and rel.parts[:1] != ("bench",)]
    for key in ("configs", "workloads"):
        assert spec[key][:len(old[key])] == old[key]
        assert len(spec[key]) == len(old[key]) + 1
    for key in ("end_to_end", "per_layer"):
        for new, was in zip(spec[key], old[key], strict=True):
            if "workloads" in was:
                assert new["workloads"] == was["workloads"] + [MOE_CELL]
            else:
                assert new == was
    assert {k: v for k, v in spec.items() if k not in (
        "configs", "workloads", "end_to_end", "per_layer")} == {
        k: v for k, v in old.items() if k not in (
            "configs", "workloads", "end_to_end", "per_layer")}
