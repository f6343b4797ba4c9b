"""The program-span reduction and its three readers, on hand-built
traces with exact answers and on a whole small cell on the CPU."""
import argparse
import time
from types import SimpleNamespace

import pytest

from bench import harness, spans, trace
from bench.tests.tiny import bench_dir

MS = 1_000_000  # ns
#: cells with Poisson arrivals: at small sizes their traffic runs into
#: the traced tail on every seed
CELLS = ("internlm2.chat", "internlm2.docs")
READERS = ("mmu_idle_ms", "engine_idle_ms", "vmm_mediate_us")


def events(program=True):
    """Gaps (2, 4), (6, 8), (10, 12), (16, 18) ms and a 10 us one; two
    whole engine steps and one cut by the window's end."""
    dev = {"/device:TPU:0": [
        ("a", 0, 2 * MS), ("b", 4 * MS, 6 * MS), ("c", 8 * MS, 10 * MS),
        ("d", 12 * MS, 13 * MS), ("e", 13.01 * MS, 16 * MS),
        ("f", 18 * MS, 20 * MS)]}
    host = [(trace.WINDOW_SPAN, 0, 20 * MS),
            ("bench.step", 0.9 * MS, 9.6 * MS),
            ("bench.wait", 10 * MS, 12 * MS),
            ("bench.step", 12.4 * MS, 17.6 * MS),
            # a profiler Python frame, shorter than any span around it
            ("$engine.py:650 step", 16.5 * MS, 17.2 * MS)]
    if program:
        host += [("engine.step", 1 * MS, 9.5 * MS),
                 ("engine.admit", 2 * MS, 3.5 * MS),
                 ("kv.admit", 2.5 * MS, 3.2 * MS),
                 ("mmu.alloc_pages", 2.8 * MS, 3.1 * MS),
                 ("vmm.run", 4 * MS, 6.5 * MS),
                 ("vmm.program", 4.5 * MS, 6 * MS),
                 ("engine.fetch", 6.5 * MS, 7.5 * MS),
                 ("engine.step", 12.5 * MS, 17.5 * MS),
                 ("vmm.run", 13 * MS, 15.5 * MS),
                 ("vmm.program", 13.2 * MS, 14 * MS),
                 ("vmm.program", 14.5 * MS, 15 * MS),
                 ("engine.step", 19 * MS, 21 * MS)]
    return trace.from_events(dev, host)


def read(name, tr):
    return harness.load_metric(name).read(SimpleNamespace(trace=tr))


def test_gaps_go_to_the_innermost_program_span():
    idle = spans.idle_by_span(events())
    # (2, 4): mmu.alloc_pages inside kv.admit inside engine.admit
    assert idle == {"mmu.alloc_pages": pytest.approx(2e-3),
                    "engine.fetch": pytest.approx(2e-3),
                    spans.NONE: pytest.approx(2e-3),      # bench.wait only
                    "engine.step": pytest.approx(2e-3)}   # no finer span
    assert spans.steps(events()) == 2


def test_readers_give_exact_values():
    tr = events()
    assert read("mmu_idle_ms", tr) == pytest.approx(1.0)       # 2 ms / 2
    assert read("engine_idle_ms", tr) == pytest.approx(2.0)    # 4 ms / 2
    # (2.5 - 1.5) and (2.5 - 0.8 - 0.5) ms of vmm.run outside vmm.program
    assert read("vmm_mediate_us", tr) == pytest.approx(1100.0)


def test_coverage_splits_idle_inside_steps():
    c = spans.coverage(events())
    assert c == {"idle_s": pytest.approx(8e-3),
                 "in_step_s": pytest.approx(6e-3),
                 "bare_step_s": pytest.approx(2e-3),
                 "none_s": pytest.approx(2e-3)}


def test_a_trace_without_program_spans_reads_nothing():
    tr = events(program=False)
    assert spans.idle_by_span(tr) == {spans.NONE: pytest.approx(8e-3)}
    assert all(read(name, tr) is None for name in READERS)


def test_union_of_intervals():
    assert spans.union_ns([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert spans.union_ns([]) == 0


# ---------------------------------------------------------------------------
# A whole small cell on the CPU
# ---------------------------------------------------------------------------


def load_cpu(path):
    """``trace.load``, with the CPU client's executor threads standing in
    for the device: the CPU backend writes no device plane."""
    from jax.profiler import ProfileData
    tr = LOAD(path)
    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines if line.name.startswith("tf_XLA")
           for e in line.events if e.duration_ns > 0]
    return trace.Trace(tr.window, {"/device:TPU:0": ops}, tr.host_spans)


LOAD = trace.load


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return bench_dir(tmp_path_factory.mktemp("bench"), CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_small_cell_reports_the_span_metrics(bench, name, monkeypatch,
                                             tmp_path):
    monkeypatch.setattr(trace, "load", load_cpu)
    monkeypatch.setattr(harness, "RUNS", tmp_path)   # no earlier trace
    args = argparse.Namespace(workload=name, seed=2 ** 31 + 13, seconds=3,
                              trace=1)
    res = harness.run_cell(args, time.perf_counter(), require_tpu=False,
                           bench=bench, cache=False)
    assert res["correct"], res["checks"]
    for metric in READERS:
        assert res["metrics"][metric]["value"] >= 0, metric
    assert res["metrics"]["vmm_mediate_us"]["value"] > 0
