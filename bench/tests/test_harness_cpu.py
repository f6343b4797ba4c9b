"""A whole run of a cell at small widths on the CPU, with the look for a
chip skipped: sound runs come out correct, and each fault the serving
path can have, planted underneath the timed path, comes out not correct.
"""
import argparse
import copy
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.tiny import bench_dir

SEED = 2 ** 31 + 11
CELLS = ("internlm2.chat", "rwkv6.burst")


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return bench_dir(tmp_path_factory.mktemp("bench"), CELLS)


def run(bench, name, trace=0, fault=None, control=False, seed=SEED,
        seconds=3, workload=None):
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace)
    return harness.run_cell(args, time.perf_counter(), require_tpu=False,
                            bench=bench, fault=fault, control=control,
                            cache=False, workload=workload)


@pytest.mark.parametrize("name,trace", [("internlm2.chat", 0),
                                        ("rwkv6.burst", 1)])
def test_sound_run_is_correct_and_reports_its_metrics(bench, name, trace):
    res = run(bench, name, trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    e2e, layer = harness.cell_metrics(harness.load_manifest(), name)
    assert set(res["metrics"]) <= set(layer if trace else e2e)
    if not trace:
        assert set(res["metrics"]) == set(e2e)
        assert res["metrics"]["setup_s"]["value"] > 0
    else:
        assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_traced_run_whose_traffic_ends_before_the_tail_fails(bench):
    """One request, due at 0 s and served in well under the 5 s before
    the traced tail: the run has no trace of its own and says so."""
    wl = copy.deepcopy(harness.load_workload("internlm2.chat", bench))
    wl["traffic"]["arrivals"]["rate_per_s"] = 0.2
    wl["traffic"]["preroll_s"] = 5
    with pytest.raises(harness.NoTrace, match="before the traced tail"):
        run(bench, "internlm2.chat", trace=1, seconds=1, workload=wl)


def _state_unchanged(attr):
    def plant(engine):
        orig = getattr(engine, attr)

        def f(params, state, *a):
            out, _ = orig(params, jax.tree.map(jnp.copy, state), *a)
            return out, state
        setattr(engine, attr, f)
    return plant


def _tokens_altered(engine):
    orig = engine._fused_fn

    def f(*a):
        toks, state = orig(*a)
        return (toks + 1) % engine.cfg.vocab, state
    engine._fused_fn = f


def _half_batch_dropped(engine):
    orig = engine._fused_fn

    def f(params, state, token, positions, *a):
        # the first half: admission fills the lowest free slots, so at
        # low load those hold most requests
        half = jnp.arange(positions.shape[0]) < positions.shape[0] // 2
        return orig(params, state, token, jnp.where(half, -1, positions), *a)
    engine._fused_fn = f


FAULTS = {"decode_state_unchanged": _state_unchanged("_fused_fn"),
          "prefill_state_unchanged": _state_unchanged("_chunk_fn"),
          "token_altered": _tokens_altered,
          "half_batch_dropped": _half_batch_dropped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_underneath_the_timed_path_is_not_correct(bench, fault):
    res = run(bench, "internlm2.chat", fault=FAULTS[fault])
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_float8_control_reads_a_wider_gap_than_the_program(bench, name):
    """The control, put in the program's place and judged by the same
    rule, comes out not correct where the program is correct."""
    res = run(bench, name, control=True)
    prog = res["checks"]["max_logit_gap"]["value"]
    ctl = res["control"]["checks"]["max_logit_gap"]["value"]
    assert ctl > 3 * prog
    assert res["correct"], res["checks"]
    assert not res["control"]["correct"], res["control"]["checks"]
