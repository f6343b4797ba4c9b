"""Tail and rate arithmetic on hand-made timestamps."""
import math

import numpy as np
import pytest

from bench.harness import (Request, Step, Window, end_to_end, itl_values,
                           nearest_rank, tokens_per_s, ttft_values)


def test_nearest_rank():
    v = list(range(1, 101))
    assert nearest_rank(v, 0.95) == 95
    assert nearest_rank(v, 0.5) == 50
    assert nearest_rank([3.0], 0.95) == 3.0
    assert nearest_rank([1, 2, math.inf], 0.95) == math.inf
    assert math.isnan(nearest_rank([], 0.95))


def req(due, times):
    r = Request(due, np.zeros(4, np.int32), len(times))
    r.tokens_t = list(times)
    return r


def test_ttft_counts_from_the_due_time_and_misses_count_as_inf():
    rs = [req(1.0, [1.25, 1.5]), req(2.0, [2.5]), req(3.0, [])]
    assert ttft_values(rs) == [0.25, 0.5, math.inf]


def test_itl_takes_every_gap_whose_later_token_is_in_the_window():
    rs = [req(0.0, [0.5, 1.0, 1.75, 3.5]), req(1.0, [1.1, 1.2])]
    assert sorted(itl_values(rs, 1.0, 3.0)) == pytest.approx(
        [0.1, 0.5, 0.75])


def test_tokens_per_s_over_the_window_seconds():
    steps = [Step(0.0, 0.5, prefill_tokens=512, gen_tokens=3),
             Step(0.5, 1.5, gen_tokens=4),
             Step(1.5, 2.5, prefill_tokens=256, gen_tokens=4),
             Step(2.5, 3.5, gen_tokens=4)]
    assert tokens_per_s(steps, 1.0, 3.0) == pytest.approx((4 + 260) / 2.0)


def test_end_to_end_uses_requests_due_in_the_window():
    win = Window(preroll=1.0, seconds=2.0)
    rs = [req(0.5, [0.9, 1.2]),              # due before: not in ttft
          req(1.0, [1.5, 1.6, 1.8]),
          req(2.0, [])]                       # due, never served: inf
    steps = [Step(0.0, 1.5, gen_tokens=2), Step(1.5, 2.5, gen_tokens=2)]
    m = end_to_end(rs, steps, win)
    assert m["ttft_p95_ms"] == math.inf
    assert m["itl_p95_ms"] == pytest.approx(300.0)
    assert m["itl_mean_ms"] == pytest.approx((300.0 + 100.0 + 200.0) / 3)
    assert m["tokens_per_s"] == pytest.approx(2.0)
