"""The yardstick's arithmetic on the CPU: operation and byte counts
against hand-worked numbers, and every per-layer reader on a small span
list and device trace whose answers are worked out by hand here.  The
arch and the counts are the ones the configuration files name."""

import dataclasses
import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import trace_reduce  # noqa: E402
from peaks import PEAKS, peaks_for  # noqa: E402

QWEN_CELL = run.load_cell("qwen2-0.5b.embodied")
STABLELM_CELL = run.load_cell("stablelm-3b-5l.longctx")
Arch = QWEN_CELL.family.arch.Arch
counts = QWEN_CELL.family.counts   # the dense family's, as configured
QWEN = Arch(d_model=896, n_layers=24, n_heads=14, n_kv_heads=2, head_dim=64,
            d_ff=4864, vocab=151936, tied=True, norm="rmsnorm",
            norm_eps=1e-6, qkv_bias=True, rope_theta=1e6,
            rotary_fraction=1.0, split_layer=6)
STABLELM = Arch(d_model=2560, n_layers=5, n_heads=32, n_kv_heads=32,
                head_dim=80, d_ff=6912, vocab=50304, tied=False,
                norm="layernorm", norm_eps=1e-5, qkv_bias=False,
                rope_theta=1e4, rotary_fraction=1.0, split_layer=1)
V5E = PEAKS["TPU v5 lite"]


def test_configs_on_disk_are_these_archs():
    assert QWEN_CELL.arch == QWEN
    assert STABLELM_CELL.arch == STABLELM


def test_qwen2_counts_by_hand():
    # per layer: q 896·896, k and v 896·128 each, o 896·896,
    # gate/up/down 3·896·4864 = 14,909,440 weights
    assert counts.layer_matmul_params(QWEN) == 14_909_440
    # decode at 300 live positions: 2·14,909,440·24 = 715,653,120
    # + attention 4·64·14·24·300 = 25,804,800
    # + logits 2·896·151,936 = 272,269,312
    assert counts.decode_token_flops(QWEN, 300) == 1_013_727_232
    # prefill of 300 true tokens: 715,653,120·300 + attention over
    # 300·301/2 = 45,150 pairs (4·64·14·24·45,150 = 3,883,622,400)
    # + one position's logits
    assert counts.prefill_flops(QWEN, 300) == 218_851_827_712
    # decode attention at 300: codes 2·300·2·64 + scales 2·300·2·4
    # + q and out 2·896·4 = 88,768 bytes a layer, ×24
    assert counts.decode_attn_work(QWEN, 300) == (25_804_800, 2_130_432)


def test_stablelm_counts_by_hand():
    counts = STABLELM_CELL.family.counts
    # 2560·(2560 + 2·2560) + 2560·2560 + 3·2560·6912 = 79,298,560
    assert counts.layer_matmul_params(STABLELM) == 79_298_560
    # 2·79,298,560·5 + 4·80·32·5·3500 + 2·2560·50,304
    assert counts.decode_token_flops(STABLELM, 3500) == 1_229_742_080
    # 792,985,600·3500 + 4·80·32·5·(3500·3501/2) + 257,556,480
    assert counts.prefill_flops(STABLELM, 3500) == 3_089_396_756_480
    # (2·3500·32·(80 + 4) + 2·2560·4)·5 layers
    assert counts.decode_attn_work(STABLELM, 3500) == (179_200_000,
                                                       94_182_400)


def test_least_time_names_its_bound():
    t, bound = counts.least_time(2e12, 1e6, V5E)
    assert bound == "compute" and t == pytest.approx(2e12 / 197e12)
    t, bound = counts.least_time(*counts.decode_attn_work(QWEN, 300), V5E)
    assert bound == "memory" and t == pytest.approx(2_130_432 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v4")


@dataclasses.dataclass
class R:                       # what a reader sees of a request
    rid: int
    due: float
    prompt: list
    times: list


def hand_run():
    """A 1 s window: one prefill (rid 0, 300-token prompt, due at 0.05,
    admitted at 0.09) and one 3-step chunk of 2 live rows out of 16."""
    ops = [  # instruction, start, duration, is a Pallas kernel
        ("fusion.1", 0.10, 0.05, False),
        ("fusion.2", 0.15, 0.02, False),
        ("while.1", 0.21, 0.14, False),           # holds the next two
        ("closed_call.12", 0.21, 0.01, True),
        ("fusion.3", 0.25, 0.10, False),
        ("copy.1", 0.60, 0.02, False),            # outside every span
    ]
    spans = [("decode.prefill", 0.09, 0.18, {"rid": 0}),
             ("decode.chunk", 0.20, 0.50, {"live_rows": 2, "max_steps": 3})]
    instants = [("decode.admit", 0.09, {"rid": 0})]
    reqs = [R(0, 0.05, [1] * 300, [0.18, 0.5, 0.5, 0.5])]
    return types.SimpleNamespace(
        spans=spans, instants=instants, requests=reqs, window=(0.0, 1.0),
        trace={"window": [0.0, 1.0], "devices": {"/device:TPU:0": ops}},
        arch=QWEN, counts=counts, max_batch=16, peaks=V5E, code_bytes=1)


def read(name, ctx):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py").read(ctx)


def test_every_reader_on_a_hand_worked_trace():
    ctx = hand_run()
    # device busy: [0.10, 0.17] + [0.21, 0.35] + [0.60, 0.62]
    assert trace_reduce.busy_seconds(ctx.trace) == pytest.approx(0.23)
    want = {
        "queue_wait_p95_ms": 40.0,
        "batch_occupancy": 100.0 * 2 / 16,
        "prefill_ms": 90.0,
        "decode_step_ms": 100.0,
        "device_idle_share": 77.0,
        # 218,851,827,712 operations in 0.07 s of prefill ops
        "prefill_mfu": 100.0 * 218_851_827_712 / 0.07 / 197e12,
        # tokens 1..3 at 301..303 live positions, in 0.14 s of chunk ops
        "decode_mfu": 100.0 * sum(counts.decode_token_flops(QWEN, n)
                                  for n in (301, 302, 303)) / 0.14 / 197e12,
        # bytes at 301..303 over HBM bandwidth, over the kernel's 0.01 s
        "decode_attn_roofline": 100.0 * sum(
            counts.decode_attn_work(QWEN, n)[1] for n in (301, 302, 303))
        / 819e9 / 0.01,
    }
    for name, value in want.items():
        assert read(name, ctx) == pytest.approx(value), name


def test_readers_find_nothing_without_a_trace():
    ctx = hand_run()
    ctx.trace = None
    for name in ("prefill_mfu", "decode_mfu", "decode_attn_roofline",
                 "device_idle_share"):
        assert read(name, ctx) is None, name


def test_breakdown_names_ops_and_idle_time():
    b = run.breakdown(hand_run())
    ops = dict(b["device_ops"])
    assert b["device_ops"][0] == ["decode:fusion.3", pytest.approx(0.10)]
    assert ops["decode:while.1"] == pytest.approx(0.14 - 0.01 - 0.10)
    assert ops["other:copy.1"] == pytest.approx(0.02)
    idle = dict((k.split(" (")[0], v) for k, v in b["idle_gaps"])
    # gaps [0, .10] [.17, .21] [.35, .60] [.62, 1] split by the prefill
    # span [.09, .18] and the chunk span [.20, .50]
    assert idle["decode.prefill"] == pytest.approx(0.01 + 0.01)
    assert idle["decode.chunk"] == pytest.approx(0.01 + 0.15)
    assert idle["host outside engine calls"] == pytest.approx(
        0.09 + 0.02 + 0.10 + 0.38)


def test_readers_on_a_recorded_chip_trace(tmp_path):
    """85 ms of the qwen2-0.5b.embodied cell's device trace from one TPU
    v5e chip (three prefills and a decode chunk), the op names cut to
    instruction, opcode and custom-call target and written back as an
    XSpace, with the spans and requests the harness recorded alongside."""
    import gzip
    import json

    import jax
    data = BENCH / "tests" / "data"
    side = json.loads((data / "qwen2-0.5b.embodied.spans.json").read_text())
    with gzip.open(data / "qwen2-0.5b.embodied.xspace.txtpb.gz", "rt") as f:
        text = f.read()
    (tmp_path / "t.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    window = tuple(side["window"])
    trace = trace_reduce.reduce_xplane(str(tmp_path), side["mark"], window)
    ops = trace["devices"]["/device:TPU:0"]
    assert len(ops) == text.count("events {") - 1      # less the mark
    kernels = [o for o in ops if o[3]]
    assert kernels and all(o[0].startswith("%closed_call") for o in kernels)
    spans = [tuple(s) for s in side["spans"]]
    # the clock is aligned: device work sits inside the engine's calls
    inside = trace_reduce.ops_within(ops, [(s[1], s[2]) for s in spans])
    assert len(inside) > 0.99 * len(ops)
    busy = trace_reduce.busy_seconds(trace)
    assert 0 < busy < window[1] - window[0]
    reqs = [R(r["rid"], r["due"], [0] * r["prompt_len"], r["times"])
            for r in side["requests"]]
    ctx = types.SimpleNamespace(
        spans=spans, instants=[tuple(i) for i in side["instants"]],
        requests=reqs, window=window, trace=trace, arch=QWEN,
        counts=QWEN_CELL.family.counts, max_batch=16,
        peaks=peaks_for(side["device_kind"]), code_bytes=1)
    for name in ("prefill_mfu", "decode_mfu", "decode_attn_roofline",
                 "device_idle_share"):
        v = read(name, ctx)
        assert v is not None and 0 < v < 100, name
    assert read("prefill_ms", ctx) > 0 and read("decode_step_ms", ctx) > 0
    # what the readers read on this slice before the configurations named
    # their own arch reader and counts; a change to the dense family's
    # counts or to a reader shows here
    pinned = {"prefill_mfu": 15.525146020751972,
              "decode_mfu": 0.3027457462358448,
              "decode_attn_roofline": 1.0240353419377217,
              "device_idle_share": 15.749099995690209,
              "prefill_ms": 9.726000000005305,
              "decode_step_ms": 9.082500000000474,
              "batch_occupancy": 31.25}
    for name, value in pinned.items():
        assert read(name, ctx) == pytest.approx(value, rel=1e-12), name
    b = run.breakdown(ctx)
    assert b["device_ops"][0][0].startswith(("prefill:", "decode:"))
    assert b["device_ops"][0] == ["decode:%closed_call.12",
                                  pytest.approx(0.007561628999999992,
                                                rel=1e-12)]
    idle = sum(v for _, v in b["idle_gaps"])
    assert idle == pytest.approx(window[1] - window[0] - busy)
