"""Open-loop Poisson arrivals at ``rate_per_s``.

``round(rate · span)`` arrivals whose gaps are exponential at fixed
quantiles, in an order drawn from the seed."""
from __future__ import annotations

import numpy as np

from bench.traffic import stratified_gaps


def generate(params: dict, t0: float, t1: float, rng) -> np.ndarray:
    n = int(round(params["rate_per_s"] * (t1 - t0)))
    gaps = stratified_gaps(n, t1 - t0, rng)
    # the first gap starts at t0, so arrivals fill [t0, t1)
    return t0 + (np.cumsum(gaps) - gaps[0]) if n else gaps
