"""The two readers of the engine's step spans, ``step_host_ms`` and
``idle_with_work_share``: their values on a hand-worked span list and
device trace, nothing on a program without ``decode.step`` spans (the
recorded qwen2 trace), unchanged readings of the other readers there,
and the harness on the CPU."""

import gzip
import json
import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
import trace_reduce  # noqa: E402
from peaks import PEAKS, peaks_for  # noqa: E402
from test_harness import run_tiny, tiny_root  # noqa: E402,F401
from test_reduce import QWEN, QWEN_CELL, R  # noqa: E402


def read(name, ctx):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py").read(ctx)


def hand_run(devices):
    """A 1 s window and three steps:

    - step [.05, .20], opened with nothing in flight (a request submitted
      at .02 waits for it), a prefill wait [.09, .16];
    - step [.25, .45] with one request in flight, a chunk wait [.29, .41];
    - a gap in which the engine is empty until a submit at .60;
    - step [.80, .90], a chunk wait [.82, .86]; after it, a submit at .95.

    Device 0 runs ops over [.10, .15], [.30, .40] and [.70, .75]."""
    spans = [
        ("decode.step", 0.05, 0.20, {"pending": 1, "in_flight": 0}),
        ("decode.prefill", 0.08, 0.17, {"rid": 0}),
        ("decode.prefill.launch", 0.08, 0.09, {}),
        ("decode.prefill.wait", 0.09, 0.16, {}),
        ("decode.step", 0.25, 0.45, {"pending": 0, "in_flight": 1}),
        ("decode.chunk", 0.28, 0.42, {"live_rows": 1, "max_steps": 4}),
        ("decode.chunk.wait", 0.29, 0.41, {}),
        ("decode.step", 0.80, 0.90, {"pending": 1, "in_flight": 0}),
        ("decode.chunk.wait", 0.82, 0.86, {}),
    ]
    instants = [("decode.submit", t, {"rid": i})
                for i, t in enumerate((0.02, 0.60, 0.95))]
    ops = {"/device:TPU:0": [("fusion.1", 0.10, 0.05, False),
                             ("fusion.2", 0.30, 0.10, False),
                             ("fusion.3", 0.70, 0.05, False)],
           # busy through the whole window
           "/device:TPU:1": [("while.1", 0.0, 1.0, False)]}
    return types.SimpleNamespace(
        spans=spans, instants=instants, requests=[], window=(0.0, 1.0),
        trace={"window": [0.0, 1.0],
               "devices": {k: ops[k] for k in devices}},
        arch=QWEN, counts=QWEN_CELL.family.counts, max_batch=16,
        peaks=PEAKS["TPU v5 lite"], code_bytes=1)


def test_step_host_ms_by_hand():
    # (.15 - .07) + (.20 - .12) + (.10 - .04) seconds over three steps
    assert read("step_host_ms", hand_run(["/device:TPU:0"])) == \
        pytest.approx(1e3 * (0.08 + 0.08 + 0.06) / 3)


@pytest.mark.parametrize("devices,want", [
    # idle [0, .10] [.15, .30] [.40, .70] [.75, 1]; work held over
    # [.02, .45] (a submit, a step, an in-flight gap, a step),
    # [.60, .90] (a submit, a step) and [.95, 1] (a submit):
    # .08 + .15 + (.05 + .10) + (.15 + .05) = .58 of the window
    (["/device:TPU:0"], 58.0),
    # the second device never idles: the mean over the two
    (["/device:TPU:0", "/device:TPU:1"], 29.0),
])
def test_idle_with_work_share_by_hand(devices, want):
    ctx = hand_run(devices)
    assert read("idle_with_work_share", ctx) == pytest.approx(want)
    # never more than the whole idle share
    assert want <= read("device_idle_share", ctx) + 1e-9


def test_readers_of_steps_find_nothing_without_them():
    ctx = hand_run(["/device:TPU:0"])
    ctx.spans = [s for s in ctx.spans if s[0] != "decode.step"]
    assert read("step_host_ms", ctx) is None
    assert read("idle_with_work_share", ctx) is None
    ctx = hand_run(["/device:TPU:0"])
    ctx.trace = None
    assert read("idle_with_work_share", ctx) is None
    assert read("step_host_ms", ctx) is not None


def test_recorded_chip_trace_without_step_spans(tmp_path):
    """The qwen2 slice recorded before the engine had ``decode.step``
    spans: the new readers read nothing, and the eight others read what
    they read before the new readers existed."""
    import jax
    data = BENCH / "tests" / "data"
    side = json.loads((data / "qwen2-0.5b.embodied.spans.json").read_text())
    with gzip.open(data / "qwen2-0.5b.embodied.xspace.txtpb.gz", "rt") as f:
        text = f.read()
    (tmp_path / "t.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    window = tuple(side["window"])
    ctx = types.SimpleNamespace(
        spans=[tuple(s) for s in side["spans"]],
        instants=[tuple(i) for i in side["instants"]],
        requests=[R(r["rid"], r["due"], [0] * r["prompt_len"], r["times"])
                  for r in side["requests"]],
        window=window,
        trace=trace_reduce.reduce_xplane(str(tmp_path), side["mark"],
                                         window),
        arch=QWEN, counts=QWEN_CELL.family.counts, max_batch=16,
        peaks=peaks_for(side["device_kind"]), code_bytes=1)
    assert read("step_host_ms", ctx) is None
    assert read("idle_with_work_share", ctx) is None
    before = {"queue_wait_p95_ms": None, "batch_occupancy": 31.25,
              "prefill_ms": 9.726000000005305,
              "decode_step_ms": 9.082500000000474,
              "prefill_mfu": 15.525146020751972,
              "decode_mfu": 0.3027457462358448,
              "decode_attn_roofline": 1.0240353419377217,
              "device_idle_share": 15.749099995690209}
    for name, value in before.items():
        got = read(name, ctx)
        assert got == (None if value is None else pytest.approx(value)), name


def test_harness_reads_step_host_time_on_the_cpu(tiny_root, capsys):
    res, _ = run_tiny(tiny_root, capsys, trace=True)
    assert res["metrics"]["step_host_ms"]["value"] > 0
    # no device ops on the CPU
    assert "idle_with_work_share" not in res["metrics"]
