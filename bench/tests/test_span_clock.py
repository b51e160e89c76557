"""The program's spans and the profiler's annotations of them run on one
clock: after the one offset the harness takes from its ``bench.clock``
mark, every span of a whole traced slice starts and ends where its
annotation does, and the two clocks do not drift apart across it.

The data is one traced ``stablelm-3b-5l.longctx`` run on a TPU v5e chip
(a 7.6 s slice), written by ``tools/span_trace.py --keep``: the
profiler's host planes cut to the annotations of the ``decode.*`` and
``host.gc`` spans and the clock mark (an XSpace in text form), and the
spans as the program's tracer stamped them (``time.monotonic``)."""

import gzip
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "tools"))

import span_trace  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
CELL = "stablelm-3b-5l.longctx"
TOLERANCE_S = 50e-6


def profile(text: str):
    import jax
    return jax.profiler.ProfileData.from_serialized_xspace(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))


def test_spans_agree_with_their_annotations_over_a_whole_slice():
    side = json.loads((DATA / f"{CELL}.spans.json").read_text())
    with gzip.open(DATA / f"{CELL}.hostplane.txtpb.gz", "rt") as f:
        pd = profile(f.read())
    spans = [tuple(s) for s in side["spans"]]
    got = span_trace.clock_agreement(pd, side["mark"], spans)
    assert got["unmatched"] == 0
    assert got["all"]["spans"] == len(spans) > 100
    lo, hi = side["window"]
    assert hi - lo > 7.5
    # both ends of the slice hold spans, and agree there too
    for part in ("first_second", "last_second", "all"):
        assert got[part]["spans"] > 0, part
        assert got[part]["start_s"] <= TOLERANCE_S, (part, got[part])
        assert got[part]["end_s"] <= TOLERANCE_S, (part, got[part])
    # no drift of one clock against the other across the slice
    assert abs(got["last_second"]["median_start_s"]
               - got["first_second"]["median_start_s"]) <= 5e-6


def test_host_plane_cut_reads_back_on_the_same_clock(tmp_path):
    """``span_trace.hostplane_text`` keeps the named events of a profile
    at their times."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.clock"):
            pass
        with jax.profiler.TraceAnnotation("decode.step"):
            with jax.profiler.TraceAnnotation("decode.chunk.wait"):
                jax.numpy.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("other"):
            pass
    finally:
        jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    names = {"bench.clock", "decode.step", "decode.chunk.wait"}
    want = span_trace.annotations(pd, names)
    cut = profile(span_trace.hostplane_text(pd, names))
    got = span_trace.annotations(cut, names | {"other"})
    assert set(got) == names
    for name in names:
        assert got[name] == pytest.approx(want[name], abs=1e-9), name
