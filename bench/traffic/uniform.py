"""Uniform lengths over ``[min, max]`` in multiples of ``multiple``."""
from __future__ import annotations

import numpy as np

from bench.traffic import quantile_points


def generate(params: dict, n: int, rng) -> np.ndarray:
    m = int(params.get("multiple", 1))
    lo, hi = int(params["min"]), int(params["max"])
    values = np.arange(lo, hi + 1, m)
    idx = np.floor(quantile_points(n) * len(values)).astype(np.int64)
    return rng.permutation(values[idx])
