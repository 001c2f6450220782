"""Stratified draws shared by the traffic generators.

Every seed gets the same multiset of sizes and gaps, in another order: the
values are the distribution's quantiles at ``(i + 0.5) / n`` and the seed
only permutes them. Runs with different seeds then do the same work, so
their spread measures the system and not the draw.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """Lengths from a lognormal with ``median`` and ``sigma``, clipped to
    ``[min, max]`` (ascending; permute them with the seed's generator)."""
    z = np.array([NormalDist().inv_cdf(u) for u in quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def uniform_lengths(spec: dict, n: int) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    return np.round(lo + (hi - lo) * quantiles(n)).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    return -np.log1p(-quantiles(n)) / rate


def generator(seed: int) -> np.random.Generator:
    """The traffic's generator for ``seed`` (any non-negative integer)."""
    return np.random.default_rng([seed, 0x7AFF1C])
