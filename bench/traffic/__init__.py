"""Traffic generators, found by the ``kind`` a workload file names.

Every kind is a module here with one ``generate`` function. Arrival
kinds take ``(params, t0, t1, rng)`` and return sorted due times in
``[t0, t1)`` seconds, as many as the span's length fixes; length kinds
take ``(params, n, rng)`` and return ``n`` ints.

A schedule is made of segments (the served but unmeasured preroll, then
the measured window), and each segment gets the same multiset of gaps
and lengths for every seed, drawn at fixed quantiles of the
distribution, in another order: the seed changes the order of the work,
not its amount, so runs with different seeds measure the same load.
"""
from __future__ import annotations

import importlib

import numpy as np


def load(kind: str):
    """The generator module of traffic kind ``kind``."""
    return importlib.import_module(f"bench.traffic.{kind}")


def quantile_points(n: int) -> np.ndarray:
    """``n`` probabilities at the midpoints of ``n`` equal strata."""
    return (np.arange(n) + 0.5) / n


def stratified_gaps(n: int, span_s: float, rng) -> np.ndarray:
    """``n`` exponential gaps at fixed quantiles, scaled to sum to
    ``span_s`` and shuffled by ``rng``."""
    if n <= 0:
        return np.zeros(0)
    gaps = -np.log1p(-quantile_points(n))
    gaps *= span_s / gaps.sum()
    return rng.permutation(gaps)


def round_lengths(x, params) -> np.ndarray:
    """Clip to ``[min, max]`` and round up to a multiple of ``multiple``."""
    m = int(params.get("multiple", 1))
    x = np.clip(np.asarray(x, np.float64), params["min"], params["max"])
    x = np.ceil(x / m) * m
    return np.clip(x, params["min"], params["max"]).astype(np.int64)


def generate_requests(traffic: dict, segments, vocab: int, rng):
    """The whole schedule of one run: due times, prompts, output budgets,
    each segment ``(t0, t1)`` of ``segments`` drawn on its own.

    → list of ``(due_s, prompt (S,) int32, max_new_tokens)`` sorted by
    due time."""
    arr = traffic["arrivals"]
    pl = traffic["prompt"]
    ol = traffic["output"]
    out = []
    for t0, t1 in segments:
        if t1 <= t0:
            continue
        due = load(arr["kind"]).generate(arr, t0, t1, rng)
        n = len(due)
        plens = load(pl["kind"]).generate(pl, n, rng)
        olens = load(ol["kind"]).generate(ol, n, rng)
        for t, p, o in zip(due, plens, olens):
            prompt = rng.integers(0, vocab, size=int(p), dtype=np.int64)
            out.append((float(t), prompt.astype(np.int32), int(o)))
    return out
