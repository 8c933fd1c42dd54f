"""Arrivals, pools and samples are the seed's alone."""
from __future__ import annotations

import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import traffic  # noqa: E402

BIG = 2**31 + 12345


def test_arrivals_repeat_per_seed_and_keep_their_count():
    a = traffic.arrival_times(BIG, 400.0, 20.0)
    assert np.array_equal(a, traffic.arrival_times(BIG, 400.0, 20.0))
    b = traffic.arrival_times(7, 400.0, 20.0)
    assert len(a) == len(b) == 8000
    assert not np.array_equal(a, b)
    assert a[0] == 0.0 and a[-1] < 20.0 and np.all(np.diff(a) >= 0)
    ga = np.sort(traffic.poisson_gaps(BIG, 400.0, 20.0))
    assert np.allclose(ga, np.sort(traffic.poisson_gaps(7, 400.0, 20.0)))
    assert abs(np.mean(ga) * 400.0 - 1.0) < 1e-9


def test_pools_picks_and_samples_repeat_per_seed():
    p = traffic.image_pool(BIG, 3, 8)
    assert p.shape == (3, 8, 8, 3) and p.dtype == np.float32
    assert np.array_equal(p, traffic.image_pool(BIG, 3, 8))
    assert not np.array_equal(p, traffic.image_pool(BIG + 1, 3, 8))
    assert np.array_equal(traffic.picks(BIG, 50, 7),
                          traffic.picks(BIG, 50, 7))
    assert traffic.sample(BIG, range(100), 10) == \
        traffic.sample(BIG, range(100), 10)
    assert traffic.sample(1, range(5), 10) == list(range(5))
    assert 0 <= traffic.jax_seed(BIG, 0) < 2**31
