"""The open-loop schedule and the accounting of how late it ran."""
import numpy as np
import pytest

import loadgen

BIG_SEED = 3_000_000_017          # more than 32 signed bits hold


def sched(seed, rate=500.0, seconds=4.0):
    return loadgen.zipf_poisson(1000, rate, seconds, 1.1, 1, seed)


def test_rate_and_length():
    s = sched(BIG_SEED)
    assert len(s) == 2000
    assert np.all(np.diff(s.t) >= 0) and s.t[0] > 0
    assert s.t[-1] == pytest.approx(4.0)
    assert np.all((s.nodes >= 0) & (s.nodes < 1000))


def test_same_seed_same_schedule():
    a, b = sched(BIG_SEED), sched(BIG_SEED)
    np.testing.assert_array_equal(a.t, b.t)
    np.testing.assert_array_equal(a.nodes, b.nodes)


def test_seeds_share_the_work_in_another_order():
    a, b = sched(7), sched(BIG_SEED)
    assert not np.array_equal(a.nodes, b.nodes)
    np.testing.assert_array_equal(np.sort(a.nodes), np.sort(b.nodes))
    np.testing.assert_allclose(np.sort(np.diff(a.t, prepend=0.0)),
                               np.sort(np.diff(b.t, prepend=0.0)))


def test_popularity_is_skewed():
    counts = np.bincount(sched(5, rate=5000.0).nodes, minlength=1000)
    top = np.sort(counts)[::-1]
    assert top[:10].sum() > 0.25 * counts.sum()     # Zipf 1.1 head
    assert (counts > 0).sum() > 300                 # and a long tail


def test_lateness_and_percentiles():
    t = np.array([0.0, 0.1, 0.2, 0.3])
    late = loadgen.lateness(t, t + np.array([0.0, 0.002, 0.0, 0.010]))
    np.testing.assert_allclose(late, [0.0, 0.002, 0.0, 0.010])
    assert loadgen.percentile(late, 50) == pytest.approx(0.001)
    assert loadgen.percentile([1.0, np.inf], 100) == np.inf
    with pytest.raises(ValueError):
        loadgen.lateness(t, t - 0.001)
