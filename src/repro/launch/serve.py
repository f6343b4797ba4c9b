"""Serving driver: continuous batching over the paged KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --requests 8 --max-new 16
    ... --virtualized   # route steps through the VMM data plane
    ... --virtualized --policy wfq   # weighted-fair-queued data plane
    ... --virtualized --policy slo --slo-ms 50   # deadline-scheduled
                      # data plane + MMU-pressure admission gate

Requests are submitted with varying prompt lengths and token budgets;
the engine admits them into batch slots as earlier requests hit EOS —
each newcomer prefills alone into pages leased from the MMU, so slot
recycling and page faults are visible in the completion log. Under
``--virtualized`` the KV pages lease real segments from the tenant's
``SegmentPool``, so ``vmm.stats()["memory"]`` shows serving memory as
tenant-accountable pages.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main(argv=None):
    """Serve the requests ``argv`` describes; returns the engine, the
    finished requests and the wall time, for callers that check them."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="chunked prefill budget per engine step (0 = "
                         "monolithic admission); also switches decode to "
                         "the fused attention+sampling step")
    ap.add_argument("--share-prefix", action="store_true",
                    help="refcounted prompt-prefix sharing: requests "
                         "with a common prompt prefix map the same KV "
                         "pages and skip prefill for the shared span "
                         "(requires --chunk-tokens)")
    ap.add_argument("--swap", action="store_true",
                    help="host-memory KV swap tier: under admission "
                         "pressure a victim slot's pages move to host "
                         "memory instead of the newcomer being deferred "
                         "(requires --chunk-tokens)")
    ap.add_argument("--models", default="",
                    help="comma-separated archs for multi-model serving "
                         "on one shared pool (model multiplexing plane); "
                         "overrides --arch and ignores --virtualized")
    ap.add_argument("--max-resident", type=int, default=0,
                    help="with --models: weight-residency budget — idle "
                         "families past this count hot-swap their "
                         "weights to the host tier (0 = unlimited)")
    ap.add_argument("--mux-pool-pages", type=int, default=0,
                    help="with --models: shared MMU pool size in pages "
                         "(0 = auto-size so every family fits)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--virtualized", action="store_true")
    ap.add_argument("--policy", default="hybrid",
                    choices=["fev", "bev", "hybrid", "wfq", "slo"])
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="per-op wait budget for --policy slo")
    ap.add_argument("--metrics", action="store_true",
                    help="enable the telemetry plane (request spans, "
                         "unified metrics registry, flight recorder); "
                         "prints the Prometheus exposition at exit")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    from repro.obs import ObsHub
    from repro.serving import ServeEngine

    enable_compile_cache()
    obs = ObsHub(enabled=args.metrics)

    if args.models:
        _serve_mux(args, obs)
        return None

    cfg = get_config(args.arch, reduced=not args.full)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    cap = args.capacity
    extra = {}
    rng = np.random.default_rng(0)
    if cfg.family == "vlm":
        extra["patches"] = jax.numpy.asarray(rng.standard_normal(
            (args.batch, cfg.frontend.n_tokens, cfg.frontend.d_in),
            dtype=np.float32))
    if cfg.is_encdec:
        extra["frames"] = jax.numpy.asarray(rng.standard_normal(
            (args.batch, cfg.frontend.n_tokens, cfg.frontend.d_in),
            dtype=np.float32))

    if args.virtualized:
        from jax.sharding import Mesh
        from repro.core import VMM
        from repro.serving import pool_pressure_gate
        devs = np.array(jax.devices()[:1]).reshape(1, 1)
        vmm = VMM(Mesh(devs, ("data", "model")), policy=args.policy,
                  obs=obs)
        vm_kw = {}
        if args.policy == "slo":
            vm_kw["sched_slo_wait_s"] = args.slo_ms / 1e3
        tenant = vmm.create_vm("server", (1, 1), **vm_kw)
        tenant.device.open()

        class _Prog:
            def __init__(self, fn):
                self.fn = fn

            def __call__(self, *a):
                return self.fn(*a)

        # every prefill/decode step passes through the VMM data plane,
        # and KV pages lease real segments from the tenant's MMU pool
        def mediate(fn):
            prog = _Prog(fn)

            def run(*a):
                tenant.program = prog
                return tenant.device.run(*a)
            return run

        # newcomers defer under pool pressure instead of bouncing on
        # MMUError — the admission hook reads the tenant's MMU stats
        engine = ServeEngine(cfg, model, args.batch, cap,
                             page_size=args.page_size, pool=tenant.pool,
                             prefill_wrap=mediate, decode_wrap=mediate,
                             admission_gate=pool_pressure_gate(tenant.pool),
                             extra_batch=extra, obs=obs,
                             obs_tenant="server",
                             chunk_tokens=args.chunk_tokens,
                             share_prefix=args.share_prefix,
                             swap=args.swap)
    else:
        engine = ServeEngine(cfg, model, args.batch, cap,
                             page_size=args.page_size, extra_batch=extra,
                             obs=obs, obs_tenant="server",
                             chunk_tokens=args.chunk_tokens,
                             share_prefix=args.share_prefix,
                             swap=args.swap)

    for i in range(args.requests):
        plen = args.prompt_len + int(rng.integers(0, 8))
        prompt = rng.integers(0, cfg.vocab, size=(plen,))
        # skew token budgets so slots free at different steps and the
        # engine's mid-decode admission actually kicks in
        budget = max(1, args.max_new - 4 * (i % 3))
        engine.submit(prompt, max_new_tokens=budget,
                      temperature=0.0 if i % 2 == 0 else 0.8)

    t0 = time.perf_counter()
    finished = []
    new_tokens = 0
    while engine.has_work():
        for r in engine.step(params):
            finished.append(r)
            new_tokens += len(r.out_tokens)
            print(f"[serve] req {r.rid}: prompt {len(r.prompt)} tok → "
                  f"{len(r.out_tokens)} new: {r.out_tokens[:8]}…")
    dt = time.perf_counter() - t0
    s = engine.stats
    print(f"[serve] {len(finished)} requests, {new_tokens} tokens in "
          f"{dt:.2f}s ({new_tokens / max(dt, 1e-9):.1f} tok/s)")
    print(f"[serve] engine: {s.steps} steps, {s.prefills} newcomer "
          f"prefills (full={s.full_prefills}, "
          f"chunks={s.prefill_chunks}), {s.page_faults} page "
          f"faults, {s.pages_leased} pages leased / {s.pages_freed} freed, "
          f"{s.deferred} deferred")
    if args.share_prefix or args.swap:
        print(f"[serve] kv hierarchy: {s.shared_prefix_hits} warm "
              f"admissions ({s.shared_prefix_tokens} shared tokens), "
              f"{s.cow_forks} CoW forks, {s.swap_outs} pages swapped / "
              f"{s.swap_ins} refaulted")
    print(f"[serve] kv memory: {engine.kv.memory_stats()}")
    if args.metrics:
        snap = obs.tracer.snapshot()
        for name, ts in snap["tenants"].items():
            ttft = ts["ttft_s"]
            qw = ts["queue_wait_s"]
            print(f"[obs] {name}: {ts['finished']} finished, "
                  f"{ts['tokens']} tokens; "
                  f"ttft p50={1e3 * ttft['p50']:.1f}ms "
                  f"p95={1e3 * ttft['p95']:.1f}ms; "
                  f"queue-wait p50={1e3 * qw['p50']:.1f}ms"
                  if ttft and qw else f"[obs] {name}: {ts}")
        if obs.flight.dumps:
            print(f"[obs] flight-recorder dumps: "
                  f"{[d['reason'] for d in obs.flight.dumps]}")
        print("[obs] prometheus exposition:")
        print(obs.prometheus())
    if args.virtualized:
        print("[serve] vmm stats:", vmm.stats())
        vmm.shutdown()
    return {"engine": engine, "model": model, "params": params,
            "finished": finished, "seconds": dt}


def _serve_mux(args, obs):
    """--models: one VMM-style host, several model families as
    registered bitstreams, tenants bound per family, one shared pool."""
    from repro.serving import ModelRegistry, MuxEngine

    names = [n.strip() for n in args.models.split(",") if n.strip()]
    reg = ModelRegistry(obs=obs,
                        max_resident=args.max_resident or None)
    for name in names:
        reg.register(name, reduced=not args.full)
    mux = MuxEngine(reg, names, batch_per_model=args.batch,
                    capacity=args.capacity, page_size=args.page_size,
                    chunk_tokens=max(args.chunk_tokens, 8),
                    pool_pages=args.mux_pool_pages or None, obs=obs)
    rng = np.random.default_rng(0)
    for i, name in enumerate(names):
        mux.bind(f"tenant{i}", name)
    for i in range(args.requests):
        name = names[i % len(names)]
        vocab = reg[name].cfg.vocab
        plen = args.prompt_len + int(rng.integers(0, 8))
        prompt = rng.integers(0, vocab, size=(plen,))
        mux.submit(prompt, tenant=f"tenant{names.index(name)}",
                   max_new_tokens=max(1, args.max_new - 4 * (i % 3)))
    t0 = time.perf_counter()
    finished = mux.run_round()
    dt = time.perf_counter() - t0
    s = mux.stats()
    total = sum(g["tokens"] for g in s["groups"].values())
    print(f"[mux] {len(names)} families, "
          f"{sum(len(v) for v in finished.values())} requests, "
          f"{total} tokens in {dt:.2f}s "
          f"({total / max(dt, 1e-9):.1f} tok/s)")
    for name, g in s["groups"].items():
        e = g["engine"]
        print(f"[mux] {name}: {g['completed']} done, {g['tokens']} tok "
              f"in {g['active_s']:.2f}s active; "
              f"pages {e['pages_leased']}/{e['pages_freed']} "
              f"state {e['state_pages_leased']}/{e['state_pages_freed']} "
              f"swaps kv={e['swap_outs']}/{e['swap_ins']} "
              f"state={e['state_swap_outs']}/{e['state_swap_ins']}")
    r = s["registry"]
    print(f"[mux] registry: {r['resident']}/{len(names)} resident "
          f"(budget {r['max_resident']}), crc {r['crc_checks']} checks / "
          f"{r['crc_failures']} failures")
    for name, m in r["models"].items():
        print(f"[mux]   {name}: resident={m['resident']} "
              f"swap in/out={m['swap_ins']}/{m['swap_outs']} "
              f"({m['param_bytes'] / 1e6:.1f} MB, crc {m['crc']})")
    print(f"[mux] pool: {s['pool']}")
    if args.metrics:
        print("[obs] prometheus exposition:")
        print(obs.prometheus())


if __name__ == "__main__":
    main()
