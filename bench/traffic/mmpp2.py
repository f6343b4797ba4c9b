"""Two-state modulated Poisson arrivals, in the style of BurstGPT
(arXiv:2401.17644): a low state at ``low_rate`` and a high state at
``high_factor`` times it, dwelling ``low_s`` and ``high_s`` seconds in
turn, starting in the low state at time 0. ``rate_per_s`` is the mean
rate over a whole cycle. Dwell times are fixed and each phase gets its
share of arrivals, with exponential gaps at fixed quantiles in an
order drawn from the seed."""
from __future__ import annotations

import numpy as np

from bench.traffic import stratified_gaps


def generate(params: dict, t0: float, t1: float, rng) -> np.ndarray:
    hi, lo = float(params["high_s"]), float(params["low_s"])
    f = float(params["high_factor"])
    low_rate = params["rate_per_s"] * (hi + lo) / (f * hi + lo)
    # phases run from time 0; only their parts inside [t0, t1) are drawn,
    # each with the rounded growth of the arrivals expected since time 0
    out, start, before, high = [], 0.0, 0.0, False
    while start < t1:
        rate = f * low_rate if high else low_rate
        end = start + (hi if high else lo)
        a, b = max(start, t0), min(end, t1)
        if b > a:
            n = (round(before + rate * (b - start))
                 - round(before + rate * (a - start)))
            gaps = stratified_gaps(n, b - a, rng)
            out.extend(a + (np.cumsum(gaps) - gaps[0]) if n else [])
        before += rate * (end - start)
        start = end
        high = not high
    return np.asarray(out)
