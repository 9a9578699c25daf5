"""The benchmark's copied traffic generators: seeded, and at their rate."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import traffic  # noqa: E402

SIZES = (1000, 50, 3, 70000, 12)
MODES = ("hetero", "drift", "powerlaw", "uniform")


def _pool(mode, seed, n=256):
    return traffic.make_pool(SIZES, 13, 8, n, mode=mode, t_pad=8, seed=seed)


@pytest.mark.parametrize("mode", MODES)
def test_pool_is_deterministic_per_seed(mode):
    a, b = _pool(mode, 2**31 + 7), _pool(mode, 2**31 + 7)
    for x, y in ((a.dense, b.dense), (a.idx, b.idx), (a.mask, b.mask)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("mode", MODES)
def test_pool_differs_across_seeds(mode):
    a, b = _pool(mode, 1), _pool(mode, 2)
    assert not np.array_equal(a.dense, b.dense)
    assert not np.array_equal(a.idx, b.idx)


@pytest.mark.parametrize("mode", MODES)
def test_pool_indices_lie_in_their_tables(mode):
    p = _pool(mode, 5)
    for t, rows in enumerate(SIZES):
        live = p.mask[:, t] > 0
        assert live.any(axis=-1).all()      # every bag holds an index
        assert (p.idx[:, t][live] < rows).all()
        assert (p.idx[:, t][live] >= 0).all()
    assert not p.mask[:, len(SIZES):].any()  # padding tables stay empty
    np.testing.assert_array_equal(p.valid(), p.mask.sum(axis=(1, 2)))


def test_pool_matches_the_programs_generator():
    """The copy draws what ``make_batch`` of the program draws."""
    from repro.configs.base import DLRMConfig
    from repro.data import synthetic
    cfg = DLRMConfig(name="t", table_sizes=SIZES, max_hot=8)
    for mode in MODES:
        want = synthetic.make_batch(cfg, 64, mode=mode, t_pad=8, seed=9)
        got = traffic.make_pool(SIZES, 13, 8, 64, mode=mode, t_pad=8,
                                seed=9)
        np.testing.assert_array_equal(got.idx, want.idx)
        np.testing.assert_array_equal(got.mask, want.mask)
        np.testing.assert_array_equal(got.dense, want.dense)


def test_schedule_is_deterministic_and_seeded():
    a = traffic.schedule(640.0, 20.0, seed=3)
    np.testing.assert_array_equal(a, traffic.schedule(640.0, 20.0, seed=3))
    assert not np.array_equal(a, traffic.schedule(640.0, 20.0, seed=4))


@pytest.mark.parametrize("rate", [50.0, 640.0, 10000.0])
def test_poisson_schedule_hits_its_rate(rate):
    seconds = 20.0
    for seed in (1, 2**31 + 11):
        t = traffic.schedule(rate, seconds, seed=seed)
        assert t.shape[0] == round(rate * seconds)     # same work per seed
        assert np.all(np.diff(t) >= 0) and t[0] > 0
        assert t[-1] == pytest.approx(seconds)
        gaps = np.diff(np.concatenate([[0.0], t]))
        # exponential gaps: coefficient of variation 1
        assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.15)
        # the arrivals spread evenly over the window, halves within 10%
        half = np.sum(t <= seconds / 2)
        assert half == pytest.approx(t.shape[0] / 2, rel=0.1)


def test_pool_order_recycles_the_whole_pool():
    order = traffic.pool_order(1000, 256, seed=8)
    assert order.shape == (1000,)
    np.testing.assert_array_equal(np.sort(order[:256]), np.arange(256))
    np.testing.assert_array_equal(order, traffic.pool_order(1000, 256,
                                                            seed=8))
