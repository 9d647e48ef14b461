"""The traffic generator: seeded, stratified, inside its bounds."""
from __future__ import annotations

import numpy as np

from bench import common, traffic as T

MIX = common.traffic_file("decode_backlog")


def test_every_seed_asks_for_the_same_work():
    n = 5 * T.BLOCK
    a = T.backlog(MIX, n, 1, vocab=1000)
    b = T.backlog(MIX, n, 2 ** 40 + 3, vocab=1000)
    assert sorted(len(r[1]) for r in a) == sorted(len(r[1]) for r in b)
    assert sorted(r[2] for r in a) == sorted(r[2] for r in b)
    assert [r[1].tolist() for r in a] != [r[1].tolist() for r in b]


def test_same_seed_same_backlog():
    a = T.backlog(MIX, 50, 7, vocab=1000)
    b = T.backlog(MIX, 50, 7, vocab=1000)
    assert all(np.array_equal(x[1], y[1]) and x[2] == y[2]
               for x, y in zip(a, b))


def test_bounds_and_order():
    reqs = T.backlog(MIX, 400, 5, vocab=92544)
    plen = [len(r[1]) for r in reqs]
    out = [r[2] for r in reqs]
    assert min(plen) >= 64 and max(plen) <= 512
    assert min(out) >= 64 and max(out) == 512
    assert max(int(r[1].max()) for r in reqs) < 92544
    assert 110 <= np.median(out) <= 150
    # submitted in the order the seed draws, block by block
    blk = T.BLOCK
    whole = T.quantiles(MIX["output_len"], blk).tolist()
    for i in range(0, 384, blk):
        assert sorted(out[i:i + blk]) == whole
    assert out[:blk] != sorted(out[:blk], reverse=True)
    assert out[:blk] != [r[2] for r in T.backlog(MIX, blk, 6, vocab=1000)]


def test_a_longer_backlog_appends():
    short = T.backlog(MIX, 100, 9, vocab=1000)
    long = T.backlog(MIX, 230, 9, vocab=1000)
    assert all(np.array_equal(x[1], y[1]) and x[2] == y[2]
               for x, y in zip(short, long))


def test_uniform_quantiles():
    q = T.quantiles({"dist": "uniform", "min": 16, "max": 64}, 49 * 10)
    assert q.min() == 16 and q.max() == 64
    assert np.bincount(q)[16:65].min() >= 9


def test_seeds_take_large_ints():
    s = common.seeds(2 ** 33 + 11, 4)
    assert len(set(s)) == 4 and all(0 <= x < 2 ** 31 for x in s)
    assert s == common.seeds(2 ** 33 + 11, 4)


def test_makespan_by_hand():
    # two slots, budgets 3, 1, 1: prefills 0.5 each, steps 1 each
    # step 1: A(3), B(1) admitted (0.5, 1.0) -> A 2, B 0; step 2: C
    # admitted (2.5) -> A 1, C 0; step 3: A 0
    assert T.schedule([3, 1, 1], 2, 1.0, 0.5) == ([0.5, 1.0, 2.5], 4.5)


def test_size_fills_the_window():
    mix = dict(MIX, slots=32)
    n = T.size(mix, 30.0, 1, 0.05, 0.015)
    admitted, _ = T.schedule(T.output_lengths(mix, n + 1, 1), 32, 0.05,
                             0.015)
    # every request is admitted by the model within OUTLAST x the window,
    # the next one later: the queue is not empty when the window closes
    assert admitted[n - 1] <= T.OUTLAST * 30.0 < admitted[n]
    assert T.size(mix, 0.01, 1, 0.05, 0.015) == 32
