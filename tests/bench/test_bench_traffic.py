"""The traffic generator: deterministic per seed, the same work for every
seed in another order, and the open loop's lateness."""
from collections import Counter

import numpy as np
import pytest

from bench import traffic
from bench.spec import Benchmark

BIG = 2**31 + 2**33 + 17  # the driver's seeds pass 32 bits


def plan(name, seed, seconds=10.0):
    bench = Benchmark()
    t = bench.traffic(name)
    return traffic.make_plan(t, seed, seconds, 512, 131072,
                             8 if name.startswith("t8") else 4)


@pytest.mark.parametrize("name", ["t4-mixed-open", "t8-mixed-open",
                                  "t4-all-closed", "t8-single-closed"])
def test_same_seed_same_plan(name):
    a, b = plan(name, BIG), plan(name, BIG)
    assert a.subsets == b.subsets
    assert np.array_equal(a.prompts, b.prompts)
    if a.due is not None:
        assert np.array_equal(a.due, b.due)


@pytest.mark.parametrize("name", ["t4-mixed-open", "t8-mixed-open"])
def test_every_seed_gets_the_same_work_in_another_order(name):
    a, b = plan(name, BIG), plan(name, BIG + 1)
    assert a.subsets != b.subsets
    assert Counter(a.subsets) == Counter(b.subsets)
    # One cycle of arrivals, entered one request later.
    assert a.subsets[1:] == b.subsets[:-1]
    assert np.allclose(np.diff(a.due)[1:], np.diff(b.due)[:-1])
    assert not np.array_equal(a.prompts, b.prompts)


def test_open_loop_rate_and_window():
    t = Benchmark().traffic("t4-mixed-open")
    p = plan("t4-mixed-open", 7)
    assert len(p.due) == round(t["rate_per_s"] * 10.0)
    assert p.due[0] == 0.0 and np.all(np.diff(p.due) > 0) and p.due[-1] < 10.0


def test_zipf_shares_of_the_mix():
    t = Benchmark().traffic("t4-mixed-open")
    counts = Counter(plan("t4-mixed-open", 3).subsets)
    w = traffic.weights(t)
    n = sum(counts.values())
    for s, share in zip(t["subsets"], w):
        assert abs(counts[tuple(s)] - share * n) < 1


def test_closed_loop_blocks_hold_the_exact_mix():
    p = plan("t8-single-closed", 5)
    for start in range(0, 800, 8):
        assert sorted(p.subsets[start:start + 8]) == [(i,) for i in range(8)]


def test_percentile_is_nearest_rank_and_counts_the_unanswered():
    assert traffic.percentile([1, 2, 3, 4], 50) == 2
    assert traffic.percentile(list(range(1, 101)), 95) == 95
    assert traffic.percentile([1.0] * 94 + [float("inf")] * 6, 95) == float("inf")


def test_lateness_reader():
    read = Benchmark().reader("generator_late_ms")

    class R:
        def __init__(self, due, sent):
            self.due, self.sent = due, sent

    class W:
        requests = [R(0.0, 0.001)] * 19 + [R(0.0, 0.010)]

    assert read(W()) == pytest.approx(1.0)
    W.requests = [R(None, 0.0)]
    assert read(W()) is None
