"""The harness end to end at a smoke size on the CPU: its result line, its
refusal to run off a TPU, cells and metrics found by name, and a broken
timed path caught by the output check.

Every run here builds a tiny copy of the benchmark in a temporary
directory: the real files plus a new configuration, mix, limit and
per-layer metric, each a new file with an entry in ``BENCHMARK.json``,
so the test also shows that adding them edits no existing file.  The
tiny cell's limit is its own: on the CPU the program and the reference
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


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_is_not_correct(tiny_root, capsys, fault):
    undo = faults.plant(fault)
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
    cfg = run.program_config(cell.config)
    limit = cell.limits["max_logit_gap"]["limit"]
    row = control.read_seed(cell, cfg, SEED, 2.0, CompiledForwardCache(),
                            True)
    assert row["program_gap"] <= limit < row["control_gap"]
    assert row["program_correct"] is True
    assert row["control_correct"] is False
