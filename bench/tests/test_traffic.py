"""The traffic generators: every seed gets the same schedule with other
token ids, open-loop arrivals fill the window at the mix's rate, and a
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
    # the same lengths and arrivals in the same order, other token ids
    assert [(len(t), m, d) for t, m, d in ra] == \
        [(len(t), m, d) for t, m, d in rb]
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(ra, rb))
    # the gaps are the exponential's quantiles, not in sorted order
    gaps = np.diff([d for _, _, d in ra] + [45.0])
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    np.testing.assert_allclose(np.sort(gaps), np.sort(q) * 45.0 / q.sum())
    assert not np.all(np.diff(gaps) >= 0)


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


def test_closed_loop_schedule_does_not_depend_on_the_seed():
    """Streams that complete in one order get the same lengths under any
    seed: the order of the lengths decides which replies end together,
    so a seed that reordered them would change the work."""
    a, mix = source("longctx", 5, 45.0)
    b, _ = source("longctx", 2 ** 32 + 17, 45.0)
    sent = []
    for s in (a, b):
        got = s.poll(0.0)
        for k in range(200):
            s.finished(0.0, 1.0 + k)
            got += s.poll(1.0 + k)
        sent.append(got)
    assert [(len(t), m) for t, m, _ in sent[0]] == \
        [(len(t), m) for t, m, _ in sent[1]]
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(*sent))
    lo, hi = mix["output_len"]
    assert len({m for _, m, _ in sent[0]}) > (hi - lo) // 2
