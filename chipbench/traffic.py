"""Traffic from a mix file and a seed: arrival schedules, the image pool and
which image each request carries, and training batches.

Every seed gets the same work in another order: the server's gaps are the
quantiles of one exponential distribution, shuffled by the seed, so each
seed offers the same number of requests over the same window.
"""
from __future__ import annotations

import math

import numpy as np

KINDS = ("server", "offline", "train")


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any seed up to 2**64."""
    return np.random.default_rng([stream, int(seed) % 2**64])


def jax_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for ``jax.random`` drawn from a run's seed."""
    return int(rng(seed, stream).integers(0, 2**31 - 1))


def poisson_gaps(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Gaps of an open-loop Poisson stream with ``rate * seconds`` arrivals
    spanning ``seconds``: the exponential distribution's quantiles at
    (i + 1/2) / n, scaled to sum to the window and shuffled by the seed."""
    n = max(int(round(rate_per_s * seconds)), 1)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= seconds / gaps.sum()
    return rng(seed, 1).permutation(gaps)


def arrival_times(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times, from the window's start, of every request in it."""
    gaps = poisson_gaps(seed, rate_per_s, seconds)
    due = np.cumsum(gaps) - gaps[0]          # the first is due at once
    return due[due < seconds]


def image_pool(seed: int, n: int, hw: int) -> np.ndarray:
    """``n`` seeded host images (n, hw, hw, 3), standard normal f32."""
    return rng(seed, 2).standard_normal((n, hw, hw, 3), dtype=np.float32)


def picks(seed: int, n_requests: int, pool: int) -> np.ndarray:
    """Which pool image each request carries."""
    return rng(seed, 3).integers(0, pool, n_requests)


def sample(seed: int, ids, k: int) -> list:
    """A seeded sample of ``k`` of ``ids`` (all of them when k >= len)."""
    ids = list(ids)
    if k >= len(ids):
        return ids
    chosen = rng(seed, 4).choice(len(ids), size=k, replace=False)
    return [ids[i] for i in sorted(chosen)]


def labels(seed: int, n: int, classes: int) -> np.ndarray:
    return rng(seed, 5).integers(0, classes, n).astype(np.int32)


def check_mix(mix: dict) -> None:
    """Refuse a mix file whose kind or parameters the generators lack."""
    need = {"server": ("rate_per_s", "max_bucket", "pool_images", "sample"),
            "offline": ("bucket", "pool_images", "sample"),
            "train": ("per_chip_batch", "pool_batches", "lr", "bn_momentum",
                      "check_steps")}
    kind = mix.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {KINDS}")
    missing = [k for k in need[kind] if k not in mix]
    if missing:
        raise ValueError(f"traffic mix of kind {kind} lacks {missing}")
    if kind == "train" and mix["pool_batches"] <= mix["check_steps"]:
        raise ValueError("the batch pool must outlast the checked steps")
    if kind == "server" and not math.isfinite(mix["rate_per_s"]):
        raise ValueError("the server mix needs a finite rate")
