"""Lognormal lengths with ``median`` and log-space ``sigma``, clipped to
``[min, max]`` and rounded up to a multiple of ``multiple``."""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

from bench.traffic import quantile_points, round_lengths


def generate(params: dict, n: int, rng) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(p) for p in quantile_points(n)])
    x = params["median"] * np.exp(params["sigma"] * z)
    return rng.permutation(round_lengths(x, params))
