"""Telemetry plane: metrics registry (counters / gauges / log-bucketed
histograms, lock-striped, labeled), request-lifecycle tracer (span per
serving request with TTFT / queue-wait / tokens-per-s derivation and
denial attribution), per-tenant flight recorder (auto-dump on
degradation triggers), and the ObsHub no-op guarantee when disabled —
plus the end-to-end acceptance span chain through ``ServeEngine``
under the ``slo`` data plane."""
import tempfile
import threading

import numpy as np
import pytest

from repro.obs import (MAX_EVENTS, NULL_HUB, PHASE_ADMITTED, PHASE_DECODE,
                       PHASE_DONE, PHASE_PREFILL, PHASE_QUEUED,
                       TRIGGER_KINDS, FlightRecorder, MetricsRegistry,
                       ObsHub, RequestTracer)

# ===========================================================================
# MetricsRegistry
# ===========================================================================


def test_counter_get_or_create_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("ops_total", tenant="a")
    a.inc()
    a.inc(2)
    # same (name, labels) → same object; different labels → separate
    assert reg.counter("ops_total", tenant="a") is a
    assert reg.counter("ops_total", tenant="b") is not a
    reg.counter("ops_total", tenant="b").inc(5)
    snap = reg.snapshot()
    assert snap["counters"]["ops_total"] == {"tenant=a": 3.0,
                                             "tenant=b": 5.0}


def test_gauge_set_and_add():
    reg = MetricsRegistry()
    g = reg.gauge("queue_depth", tenant="a")
    g.set(7)
    g.add(3)
    assert g.value == 10.0
    assert reg.snapshot()["gauges"]["queue_depth"]["tenant=a"] == 10.0


def test_label_key_is_order_independent():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", tenant="a", op="run")
    c2 = reg.counter("x_total", op="run", tenant="a")
    assert c1 is c2


def test_histogram_percentiles_bracket_distribution():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    vals = [0.001 * (i + 1) for i in range(100)]       # 1ms … 100ms
    for v in vals:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 100
    assert s["sum"] == pytest.approx(sum(vals))
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(0.1)
    # log-bucketed estimates: ordered, inside the observed range, and
    # within a bucket factor (2x) of the exact percentiles
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    assert s["p50"] == pytest.approx(0.050, rel=1.0)
    assert s["p95"] == pytest.approx(0.095, rel=1.0)


def test_histogram_empty_and_single_sample():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    assert h.summary() == {"count": 0, "sum": 0.0, "mean": 0.0, "min": 0.0,
                           "max": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    h.observe(0.25)
    s = h.summary()
    # one sample: every percentile clamps to the single observation
    assert s["p50"] == s["p95"] == s["p99"] == pytest.approx(0.25)


def test_histogram_concurrent_observe_exact_count():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    n, threads = 2000, 8

    def work():
        for i in range(n):
            h.observe(1e-4 * (1 + i % 7))

    ts = [threading.Thread(target=work) for _ in range(threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert h.count == n * threads


def test_provider_register_replace_unregister():
    reg = MetricsRegistry()
    reg.register_provider("scheduler", lambda: {"policy": "slo"})
    assert reg.snapshot()["providers"]["scheduler"] == {"policy": "slo"}
    reg.register_provider("scheduler", lambda: {"policy": "wfq"})
    assert reg.snapshot()["providers"]["scheduler"] == {"policy": "wfq"}
    reg.unregister_provider("scheduler")
    assert "scheduler" not in reg.snapshot()["providers"]


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("reqs_total", tenant="a").inc(4)
    reg.gauge("depth").set(2)
    reg.histogram("lat_s", tenant="a").observe(0.01)
    text = reg.prometheus()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{tenant="a"} 4' in text
    assert "# TYPE depth gauge" in text
    assert "# TYPE lat_s summary" in text
    assert 'lat_s{quantile="0.5",tenant="a"}' in text
    assert 'lat_s_count{tenant="a"} 1' in text
    assert text.endswith("\n")


# ===========================================================================
# RequestTracer
# ===========================================================================


def test_span_chain_and_derived_metrics():
    reg = MetricsRegistry()
    tr = RequestTracer(capacity=8, registry=reg)
    tr.start("a", 0, prompt_len=16)
    tr.event("a", 0, PHASE_ADMITTED, slot=1)
    tr.token("a", 0)
    tr.event("a", 0, PHASE_DECODE)
    tr.event("a", 0, PHASE_DECODE)
    tr.token("a", 0)
    span = tr.finish("a", 0)
    assert span.phases() == [PHASE_QUEUED, PHASE_ADMITTED, PHASE_DECODE,
                             PHASE_DECODE, PHASE_DONE]
    ts = [e.t for e in span.events]
    assert ts == sorted(ts)                     # monotonic timeline
    assert span.n_tokens == 2 and span.n_decode_steps == 2
    assert span.queue_wait_s is not None and span.queue_wait_s >= 0
    assert span.ttft_s is not None and span.ttft_s >= span.queue_wait_s
    assert span.tokens_per_s is not None and span.tokens_per_s > 0
    # derived latencies landed in the shared registry
    snap = reg.snapshot()
    assert snap["histograms"]["serve_ttft_s"]["tenant=a"]["count"] == 1
    assert snap["counters"]["serve_requests_total"][
        "status=done,tenant=a"] == 1.0
    assert snap["counters"]["serve_tokens_total"]["tenant=a"] == 2.0


def test_tracer_denial_attribution():
    reg = MetricsRegistry()
    tr = RequestTracer(registry=reg)
    for rid, cause in [(0, "pool_pressure"), (1, "pool_pressure"),
                       (2, "MMUError")]:
        tr.start("a", rid)
        tr.event("a", rid, "deferred", cause=cause)
        tr.finish("a", rid, status="denied")
    snap = tr.snapshot()
    assert snap["denials"] == {"a:MMUError": 1, "a:pool_pressure": 2}
    assert reg.snapshot()["counters"]["serve_denials_total"] == {
        "cause=MMUError,tenant=a": 1.0, "cause=pool_pressure,tenant=a": 2.0}


def test_tracer_ring_evicts_oldest():
    tr = RequestTracer(capacity=3)
    for rid in range(5):
        tr.start("a", rid)
        tr.finish("a", rid)
    assert [s.rid for s in tr.spans()] == [2, 3, 4]
    assert tr.spans(rid=0) == []


def test_span_event_cap_counts_drops():
    tr = RequestTracer()
    tr.start("a", 0)
    for _ in range(MAX_EVENTS + 10):
        tr.event("a", 0, PHASE_DECODE)
    span = tr.finish("a", 0)
    assert len(span.events) == MAX_EVENTS
    assert span.dropped_events == 12      # overflow decodes + done event
    assert span.n_decode_steps == MAX_EVENTS + 10   # exact despite drops


def test_tracer_unknown_rid_is_ignored():
    tr = RequestTracer()
    tr.event("a", 99, PHASE_DECODE)
    tr.token("a", 99)
    assert tr.finish("a", 99) is None


# ===========================================================================
# FlightRecorder
# ===========================================================================


def test_flight_auto_dump_on_trigger_and_rate_limit():
    fr = FlightRecorder(capacity=8, dump_interval_s=60.0)
    assert fr.record("a", "admit", {"shape": [1, 1]}) is None   # not a trigger
    d = fr.record("a", "queue_buildup", {"depth": 80})
    assert d is not None and d["reason"] == "queue_buildup"
    # the dump contains the pre-trigger context, in order
    assert [e["kind"] for e in d["events"]] == ["admit", "queue_buildup"]
    # within the rate-limit window a second trigger records but won't dump
    assert fr.record("a", "straggler", {}) is None
    assert len(fr.dumps) == 1
    # …but another tenant has its own limiter
    assert fr.record("b", "slice_failed", {}) is not None
    snap = fr.snapshot()
    assert snap["tenants"] == {"a": 3, "b": 1}
    assert [d["reason"] for d in snap["dumps"]] == ["queue_buildup",
                                                    "slice_failed"]


def test_flight_ring_bounded_and_forget():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("a", "admit", {"i": i})
    evs = fr.events("a")
    assert [e["payload"]["i"] for e in evs] == [6, 7, 8, 9]
    d = fr.dump("a")                               # manual postmortem dump
    assert d["reason"] == "manual" and len(d["events"]) == 4
    fr.forget("a")
    assert fr.events("a") == []
    assert len(fr.dumps) == 1                      # dumps survive forget


def test_trigger_kinds_cover_degradation_paths():
    assert {"slice_failed", "queue_buildup", "straggler",
            "admission_pressure", "grow_blocked"} <= TRIGGER_KINDS


# ===========================================================================
# ObsHub
# ===========================================================================


def test_hub_disabled_is_noop():
    hub = ObsHub(enabled=False)
    hub.count("x_total", 5, tenant="a")
    hub.observe("lat_s", 0.5, tenant="a")
    hub.set_gauge("depth", 3)
    hub.flight_record("a", "queue_buildup", {"depth": 9})
    snap = hub.snapshot()
    assert snap["enabled"] is False
    assert snap["metrics"]["counters"] == {}
    assert snap["metrics"]["histograms"] == {}
    assert snap["flight"]["dumps"] == []
    assert NULL_HUB.enabled is False


def test_hub_enabled_records_and_snapshot_shape():
    hub = ObsHub(enabled=True)
    hub.count("x_total", tenant="a")
    hub.observe("lat_s", 0.01, tenant="a")
    hub.registry.register_provider("engine", lambda: {"steps": 3})
    snap = hub.snapshot()
    assert snap["enabled"] is True
    assert snap["metrics"]["counters"]["x_total"]["tenant=a"] == 1.0
    assert snap["metrics"]["providers"]["engine"] == {"steps": 3}
    assert hub.snapshot(providers=False)["metrics"].get("providers") is None


# ===========================================================================
# Acceptance: span chain through ServeEngine under the slo data plane
# ===========================================================================


def _mediate(tenant):
    class _Prog:
        def __init__(self, fn):
            self.fn = fn

        def __call__(self, *a):
            return self.fn(*a)

    def wrap(fn):
        prog = _Prog(fn)

        def run(*a):
            tenant.program = prog
            return tenant.device.run(*a)
        return run
    return wrap


def test_serve_span_chain_under_slo_plane(rng_key):
    """A request served through the VMM's ``slo`` data plane leaves a
    complete span: queued → admitted → prefill → ≥1 decode → done with
    a monotonic timeline, and the per-tenant rollup carries TTFT and
    queue-wait."""
    import jax
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core import VMM
    from repro.models import build_model
    from repro.serving import ServeEngine, pool_pressure_gate

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = build_model(cfg)
    params = model.init(rng_key)

    obs = ObsHub(enabled=True)
    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    vmm = VMM(Mesh(devs, ("data", "model")), policy="slo", obs=obs,
              ckpt_root=tempfile.mkdtemp())
    tenant = vmm.create_vm("server", (1, 1), sched_slo_wait_s=0.05)
    tenant.device.open()
    wrap = _mediate(tenant)
    try:
        eng = ServeEngine(cfg, model, 2, 64, page_size=8, pool=tenant.pool,
                          prefill_wrap=wrap, decode_wrap=wrap,
                          admission_gate=pool_pressure_gate(tenant.pool),
                          obs=obs, obs_tenant="server")
        r0 = eng.submit(np.arange(10) % cfg.vocab, max_new_tokens=4)
        eng.submit(np.arange(6) % cfg.vocab, max_new_tokens=2)
        eng.run_round(params)

        spans = obs.tracer.spans(tenant="server", rid=r0)
        assert len(spans) == 1
        span = spans[0]
        phases = span.phases()
        # the canonical lifecycle, in order
        for a, b in zip([PHASE_QUEUED, PHASE_ADMITTED, PHASE_PREFILL,
                         PHASE_DECODE, PHASE_DONE][:-1],
                        [PHASE_ADMITTED, PHASE_PREFILL, PHASE_DECODE,
                         PHASE_DONE]):
            assert phases.index(a) < phases.index(b), phases
        assert span.n_decode_steps >= 1
        assert span.status == "done"
        ts = [e.t for e in span.events]
        assert ts == sorted(ts)                  # monotonic clock, ordered
        assert span.ttft_s > 0 and span.queue_wait_s >= 0
        assert span.n_tokens == 4

        # per-tenant rollup carries the derived latencies
        roll = obs.tracer.snapshot()["tenants"]["server"]
        assert roll["finished"] == 2
        assert roll["ttft_s"]["p50"] > 0
        assert roll["queue_wait_s"]["mean"] >= 0
        # the slo plane's own telemetry flowed into the same registry
        snap = obs.registry.snapshot()
        assert snap["counters"]["plane_ops_total"][
            "op=run,status=ok,tenant=server"] > 0
        assert snap["histograms"]["plane_wait_s"]["tenant=server"][
            "count"] > 0
        # spans and engine metrics agree on token totals
        assert snap["counters"]["serve_tokens_total"]["tenant=server"] \
            == eng.stats.generated_tokens
    finally:
        vmm.shutdown()


def test_engine_deferred_span_on_pool_pressure(rng_key):
    """An admission deferred by the pressure gate leaves a ``deferred``
    event with its cause attributed — and the request still completes
    once pages recycle."""
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServeEngine

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = build_model(cfg)
    params = model.init(rng_key)
    obs = ObsHub(enabled=True)
    gate_calls = {"n": 0}

    def stingy_gate(owner, n_pages):
        gate_calls["n"] += 1
        return gate_calls["n"] > 2           # defer the first two asks

    eng = ServeEngine(cfg, model, 2, 64, page_size=8,
                      admission_gate=stingy_gate, obs=obs,
                      obs_tenant="serve")
    eng.submit(np.arange(8) % cfg.vocab, max_new_tokens=3)
    r1 = eng.submit(np.arange(8) % cfg.vocab, max_new_tokens=2)
    eng.run_round(params)
    span = obs.tracer.spans(tenant="serve", rid=r1)[0]
    assert "deferred" in span.phases()
    assert span.status == "done"             # eventually admitted + served
    snap = obs.tracer.snapshot()
    assert snap["denials"].get("serve:pool_pressure", 0) >= 1


# ===========================================================================
# Program spans on the profiler's clock
# ===========================================================================

#: every span site in the program, by name
SPAN_NAMES = ("engine.step", "engine.admit", "engine.prefill_chunks",
              "engine.inputs", "engine.block_tables", "engine.fetch",
              "engine.row_reset", "engine.emit", "kv.admit", "kv.release", "kv.ensure", "mmu.alloc_pages",
              "mmu.grow_pages", "mmu.free_pages", "mmu.memory_stats",
              "vmm.run", "vmm.program")


def test_span_is_a_shared_noop_without_a_trace(monkeypatch):
    from jax.profiler import TraceAnnotation
    from repro.obs import span, spans

    assert not TraceAnnotation.is_enabled()
    assert span("engine.step") is span("kv.ensure", rid=1, start=0)

    class Fake:
        on = False
        built = []

        @staticmethod
        def is_enabled():
            return Fake.on

        def __init__(self, name, **args):
            Fake.built.append((name, args))

    monkeypatch.setattr(spans, "TraceAnnotation", Fake)
    off = span("kv.ensure", rid=1, start=0)
    with off:
        pass
    assert off is span("engine.step") and Fake.built == []
    Fake.on = True
    assert isinstance(span("kv.ensure", rid=1, start=0), Fake)
    assert Fake.built == [("kv.ensure", {"rid": 1, "start": 0})]


def test_program_spans_land_in_the_profiler_trace(tmp_path, rng_key):
    """A chunked engine over a VMM tenant with paged recurrent state,
    served under ``jax.profiler``: every span site shows up in the trace
    the benchmark reads, the MMU's and the KV layer's inside an engine
    step, the callable inside its mediated run."""
    import sys
    from pathlib import Path

    import jax
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core import VMM
    from repro.models import build_model
    from repro.serving import ServeEngine, pool_pressure_gate

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import trace

    cfg = get_config("rwkv6-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(rng_key)
    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    vmm = VMM(Mesh(devs, ("data", "model")), policy="hybrid",
              ckpt_root=tempfile.mkdtemp())
    try:
        tenant = vmm.create_vm("server", (1, 1))
        tenant.device.open()
        wrap = _mediate(tenant)
        eng = ServeEngine(cfg, model, 2, 64, page_size=8, pool=tenant.pool,
                          prefill_wrap=wrap, decode_wrap=wrap,
                          admission_gate=pool_pressure_gate(tenant.pool),
                          chunk_tokens=8, state_paging=True)
        prompts = [np.arange(20) % cfg.vocab, np.arange(11) % cfg.vocab]
        for p in prompts:                       # compile outside the trace
            eng.submit(p, max_new_tokens=3)
        eng.run_round(params)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                # 20 prompt tokens over 8-token pages: the second chunk
                # and the decode past position 24 grow the page table
                eng.submit(prompts[0], max_new_tokens=12)
                eng.submit(prompts[1], max_new_tokens=3)
                eng.run_round(params)
        finally:
            jax.profiler.stop_trace()
    finally:
        vmm.shutdown()

    tr = trace.load(trace.latest_xplane(str(tmp_path)))
    by_name = {}
    for name, s, e in tr.host_spans:
        by_name.setdefault(name, []).append((s, e))
    assert set(SPAN_NAMES) <= set(by_name), \
        set(SPAN_NAMES) - set(by_name)

    def inside(inner, outer):
        return all(any(a <= s and e <= b for a, b in by_name[outer])
                   for s, e in by_name[inner])
    for name in SPAN_NAMES:
        if name.startswith(("kv.", "mmu.")):
            assert inside(name, "engine.step"), name
    assert inside("vmm.program", "vmm.run")
    assert len(by_name["vmm.run"]) == len(by_name["vmm.program"])
