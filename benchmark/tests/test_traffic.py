"""The generator: the same seed gives the same schedule, every seed gets
the same sizes and arrivals in an order of its own that is stratified by
blocks, and lateness is submit time minus due time."""

import math
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.layers import gen_lateness_p95_ms
from benchmark.traffic import open_loop


@pytest.fixture(scope="module")
def mix():
    return open_loop.load_mix("chat_steady")


def test_same_seed_same_schedule(mix):
    a = open_loop.schedule(mix, 2.0, 30.0, 2**31 + 9, 50257)
    b = open_loop.schedule(mix, 2.0, 30.0, 2**31 + 9, 50257)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_offers_the_same_work_in_another_order(mix):
    a = open_loop.schedule(mix, 2.0, 30.0, 1, 50257)
    b = open_loop.schedule(mix, 2.0, 30.0, 2, 50257)
    assert len(a) == len(b) == 60
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    gaps = [np.diff([0.0] + [r.due_s for r in s]) for s in (a, b)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_lengths_are_clipped_and_arrivals_fill_the_window(mix):
    s = open_loop.schedule(mix, 2.0, 30.0, 3, 50257)
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= r.prompt.size <= p["max"] for r in s)
    assert all(o["min"] <= r.max_new <= o["max"] for r in s)
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 50257
               for r in s)
    assert all(x.due_s < y.due_s for x, y in zip(s, s[1:]))
    assert math.isclose(s[-1].due_s, 30.0 - 0.25)


def test_balanced_order_spreads_every_block():
    rng = np.random.default_rng(0)
    out = open_loop.balanced_order(list(range(64)), 16, rng)
    assert sorted(out) == list(range(64))
    for b in range(4):
        blk = out[b * 16:(b + 1) * 16]
        # one value from each stratum of four consecutive sorted values
        assert sorted(v // 4 for v in blk) == list(range(16))


def test_lateness_is_submit_minus_due():
    reqs = [SimpleNamespace(due_s=float(i), submit_s=float(i) + 0.001 * i)
            for i in range(101)]
    ctx = SimpleNamespace(window=SimpleNamespace(requests=reqs))
    assert math.isclose(gen_lateness_p95_ms.read(ctx), 95.0)


def test_every_seed_draws_its_own_stratified_order(mix):
    n, block = 96, int(mix["block"])
    seen = set()
    for seed in (1, 2, 2**31 + 5):
        s = open_loop.schedule(mix, 2.0, 48.0, seed, 50257)
        lens = [r.prompt.size for r in s]
        outs = [r.max_new for r in s]
        gaps = list(np.diff([0.0] + [r.due_s for r in s]))
        seen.add((tuple(lens), tuple(outs)))
        # no seed's order is a rotation of seed 1's
        if seed != 1:
            assert all(lens[k:] + lens[:k] != first for k in range(n))
        else:
            first = lens
        # every block of consecutive requests holds one value from each
        # stratum of n/block consecutive sorted values
        for vals in (lens, outs, gaps):
            rank = {i: r for r, i in enumerate(np.argsort(vals,
                                                          kind="stable"))}
            for b in range(n // block):
                strata = sorted(rank[i] // (n // block)
                                for i in range(b * block, (b + 1) * block))
                assert strata == list(range(block))
    assert len(seen) == 3
