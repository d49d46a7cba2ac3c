"""Traffic and sampling are drawn from the seed alone."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import traffic  # noqa: E402
from traffic import input_pool  # noqa: E402

BIG = 2 ** 33 + 12345          # seeds go past 32 bits


def test_poisson_is_deterministic_and_fixed_count():
    t = traffic.load("poisson", {"arrival": "poisson", "rate_per_s": 500.0})
    a, b = t.offsets(10.0, BIG), t.offsets(10.0, BIG)
    c = t.offsets(10.0, BIG + 1)
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c) == 5000          # the same work for every seed
    assert not np.array_equal(a, c)
    assert (np.diff(a) >= 0).all() and a[0] >= 0 and a[-1] < 10.0
    # exponential gaps: mean 1/rate, coefficient of variation near 1
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1 / 500, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


def test_poisson_releases_on_schedule():
    t = traffic.load("poisson", {"arrival": "poisson", "rate_per_s": 100.0})
    assert not t.closed and t.group_sizes(4) == [1, 2, 3, 4]
    t.start(50.0, 1.0, BIG, 4)
    due = t.due
    assert len(due) == 100 and 50.0 <= due[0] and due[-1] < 51.0
    # an empty queue gets the next request early; a busy one only what is due
    assert t.release(49.0, 0, 0) == [due[0]]
    assert t.release(49.0, 1, 0) == []
    mid = float(due[40])
    out = t.release(mid, 1, 0)
    assert out == due[1:41].tolist()
    assert t.owed(41) == due[41:].tolist()


def test_backlog_and_unknown_kinds():
    t = traffic.load("backlog")
    assert t.closed and t.group_sizes(256) == [256]
    t.start(0.0, 1.0, BIG, 256)
    assert len(t.release(3.0, 0, 0)) == 512      # two full buckets queued
    assert t.release(3.0, 512, 0) == []
    assert t.release(3.0, 512, 256) == [3.0] * 256
    assert t.owed(512) == []
    with pytest.raises(ValueError, match="no arrival kind"):
        traffic.load("x", {"arrival": "bursty"})


def test_arrival_kinds_are_found_by_name():
    """Every mix file names a kind that has a module of its own."""
    mixes = os.listdir(os.path.join(BENCH, "traffic"))
    assert mixes
    for name in mixes:
        traffic.load(name[:-len(".json")])


def test_input_pool_is_seeded():
    a = input_pool((4, 3), "normal", 5, BIG)
    np.testing.assert_array_equal(a, input_pool((4, 3), "normal", 5, BIG))
    assert a.dtype == np.float32 and a.shape == (5, 4, 3)
    u = input_pool((8,), "uniform", 100, 1)
    assert u.min() >= 0 and u.max() < 1


def test_sampler_keeps_seeded_bottom_k():
    def sample(seed, chunks):
        s = harness.Sampler(seed, 16)
        rid = 0
        for n in chunks:
            s.take({r: r for r in range(rid, rid + n)})
            rid += n
        return sorted(s.outputs())

    a = sample(BIG, [256] * 40)
    assert len(a) == 16
    assert a == sample(BIG, [100] * 102 + [40])   # batching does not matter
    assert a != sample(BIG + 1, [256] * 40)
    assert max(a) > 256 * 20          # spread over the window, not its start
