"""The traffic generators: every seed gets the same work in another
order, open-loop arrivals fill the window at the mix's rate, and a
closed loop sends again only when a stream's request completes."""

import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

VOCAB = 512


def source(mix_name, seed, seconds, **over):
    mix = {**json.loads((BENCH / "mixes" / f"{mix_name}.json").read_text()),
           **over}
    gen = run.load_module(BENCH / "traffic" / f"{mix['generator']}.py")
    return gen.Source(mix, seed, seconds, VOCAB), mix


def test_open_loop_same_work_every_seed():
    a, mix = source("embodied", 2 ** 31 + 1, 45.0)
    b, _ = source("embodied", 7, 45.0)
    ra, rb = a.poll(45.0), b.poll(45.0)
    n = round(mix["rate_rps"] * 45.0)
    assert len(ra) == len(rb) == n
    assert a.next_due() is None and b.poll(1e9) == []
    for r in (ra, rb):
        due = np.array([d for _, _, d in r])
        assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 45.0
        lo, hi = mix["prompt_len"]
        assert all(lo <= len(t) <= hi and t.max() < VOCAB for t, _, _ in r)
    # the same lengths and gaps, in another order
    assert sorted(len(t) for t, _, _ in ra) == sorted(len(t) for t, _, _ in rb)
    gaps = [np.diff([d for _, _, d in r] + [45.0]) for r in (ra, rb)]
    np.testing.assert_allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert [len(t) for t, _, _ in ra] != [len(t) for t, _, _ in rb]


def test_open_loop_hands_out_each_request_once_when_due():
    s, _ = source("embodied", 3, 10.0, rate_rps=5)
    first = s.poll(0.0)
    assert len(first) == 1 and first[0][2] == 0.0
    nxt = s.next_due()
    assert s.poll(nxt - 1e-9) == []
    assert [d for _, _, d in s.poll(nxt)] == [nxt]


def test_closed_loop_sends_again_after_completion():
    s, mix = source("longctx", 11, 45.0)
    first = s.poll(0.0)
    assert len(first) == mix["streams"] and s.poll(100.0) == []
    lo, hi = mix["output_len"]
    assert all(lo <= m <= hi for _, m, _ in first)
    s.finished(0.0, 2.5)
    assert s.next_due() == 2.5 + mix["think_s"]
    again = s.poll(3.0)
    assert len(again) == 1 and again[0][2] == 2.5


def test_closed_loop_pool_is_the_same_work_every_seed():
    a, _ = source("longctx", 1, 45.0, streams=4096)
    b, _ = source("longctx", 2 ** 31 + 9, 45.0, streams=4096)
    ra, rb = a.poll(0.0), b.poll(0.0)
    assert sorted(m for _, m, _ in ra) == sorted(m for _, m, _ in rb)
    assert sorted(len(t) for t, _, _ in ra) == sorted(len(t) for t, _, _ in rb)
