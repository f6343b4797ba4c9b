"""One run of one benchmark cell: set-up, the open-loop window, the
check against the reference, and the result line.

The cell's workload file names its configuration, the path (``vmm``:
the engine's callables mediated by a VMM tenant on a one-chip slice,
pages leased from the tenant's pool; ``native``: the same engine alone),
the engine's sizes and the traffic. ``BENCHMARK.json`` names the metrics
each cell reports. Everything is found by name; nothing here knows a
cell, a configuration or a metric.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
RUNS = ROOT / ".bench_runs"          # traces; listed in .gitignore
#: JAX's persistent compilation cache, at a fixed path in the checkout
#: (the path is part of the cache key; listed in .gitignore)
CACHE = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    return json.loads(path.read_text())


def load_workload(name: str, bench: Path = BENCH) -> dict:
    wl = load_json(bench / "workloads" / f"{name}.json")
    if wl.get("name") != name:
        raise ValueError(f"workload file {name}.json names {wl.get('name')!r}")
    return wl


def load_manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_metrics(manifest: dict, cell: str):
    """(end-to-end names, per-layer names) that ``cell`` reports."""
    def mine(m):
        return "workloads" not in m or cell in m["workloads"]
    return ([m["name"] for m in manifest["end_to_end"] if mine(m)],
            [m["name"] for m in manifest["per_layer"] if mine(m)])


def units(manifest: dict) -> dict:
    return {m["name"]: m["unit"]
            for m in manifest["end_to_end"] + manifest["per_layer"]}


def load_metric(name: str, bench: Path = BENCH):
    """The reader module ``metrics/<name>.py`` (names may hold dots)."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no metric reader {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(kind: str, bench: Path = BENCH) -> dict:
    table = load_json(bench / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# Statistics
# ---------------------------------------------------------------------------


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile (0 < q ≤ 1) by nearest rank; inf counts."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


# ---------------------------------------------------------------------------
# Recording what the window does
# ---------------------------------------------------------------------------


@dataclass
class Step:
    t0: float
    t1: float = 0.0
    chunks: list = field(default_factory=list)     # (length, start array)
    lengths: Optional[np.ndarray] = None           # decode: live lengths
    prefill_tokens: int = 0
    gen_tokens: int = 0


@dataclass
class Request:
    due: float
    prompt: np.ndarray
    max_new: int
    submitted: float = math.nan
    tokens_t: list = field(default_factory=list)   # window-relative times
    out: Optional[list] = None                     # served tokens, once done


class Recorder:
    """Wraps the engine's compiled callables: notes each chunk's shape
    and each decode's live lengths, times the inner call, and opens host
    spans while a trace is on."""

    def __init__(self):
        self.steps: List[Step] = []
        self.cur: Optional[Step] = None
        self.engine = None
        self.tracing = False
        self.last_inner = 0.0
        self.vmm_calls: list = []                  # (t, outer_s, inner_s)

    def span(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def wrap(self, fn):
        def call(*args):
            step = self.cur
            if len(args) == 6 and step is not None:          # prefill chunk
                step.chunks.append((int(args[2].shape[1]), args[5]))
                step.prefill_tokens += int(args[2].shape[1])
                name = "bench.chunk"
            else:
                if step is not None:
                    pos = self.engine.positions
                    step.lengths = pos[pos >= 0].astype(np.int64) + 1
                name = "bench.decode"
            with self.span(name):
                t = time.perf_counter()
                out = fn(*args)
                self.last_inner = time.perf_counter() - t
            return out
        return call


class _Program:
    """What the VMM tenant runs: the engine's callable of this call."""

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, *a):
        return self.fn(*a)


# ---------------------------------------------------------------------------
# The system under test, wired as ``launch/serve.py --virtualized`` does
# ---------------------------------------------------------------------------


def build_engine(cfg, model, wl: dict, rec: Recorder, vmm_devices=None):
    """→ (engine, vmm or None). ``vmm`` path: a VMM on one chip with the
    workload's data-plane policy, one tenant on a (1, 1) slice, the
    chunk and fused-decode callables mediated through
    ``tenant.device.run``, KV pages and state rows leased from the
    tenant's pool under ``pool_pressure_gate``. The pool has one segment
    per KV page and as many segments as the cell's working set:
    ``slots · ceil(capacity / page_size)`` KV pages plus each slot's
    state rows. ``native`` path: the same engine with its own pool."""
    import jax
    from repro.serving import ServeEngine, pool_pressure_gate

    e = wl["engine"]
    B, cap, ps = e["slots"], e["capacity"], e["page_size"]
    kw = dict(page_size=ps, chunk_tokens=e["chunk_tokens"],
              state_paging=bool(e.get("state_paging", False)))
    if wl["path"] == "native":
        engine = ServeEngine(cfg, model, B, cap, prefill_wrap=rec.wrap,
                             decode_wrap=rec.wrap, **kw)
        rec.engine = engine
        return engine, None
    if wl["path"] != "vmm":
        raise ValueError(f"unknown path {wl['path']!r}")
    from jax.sharding import Mesh
    from repro.core import VMM

    page_bytes = model.kv_page_bytes(ps)
    pages = B * -(-cap // ps)
    if kw["state_paging"]:
        pages += B * -(-model.state_row_bytes() // page_bytes)
    devs = np.array((vmm_devices or jax.devices())[:1]).reshape(1, 1)
    vmm = VMM(Mesh(devs, ("data", "model")), policy=wl.get("policy", "hybrid"),
              hbm_per_chip=pages * page_bytes, segment_bytes=page_bytes)
    tenant = vmm.create_vm("server", (1, 1))
    tenant.device.open()

    def mediate(fn):
        prog = _Program(rec.wrap(fn))

        def run(*a):
            tenant.program = prog
            with rec.span("bench.vmm_run"):
                t = time.perf_counter()
                out = tenant.device.run(*a)
                outer = time.perf_counter() - t
            rec.vmm_calls.append((t, outer, rec.last_inner))
            return out
        return run

    engine = ServeEngine(cfg, model, B, cap, pool=tenant.pool,
                         prefill_wrap=mediate, decode_wrap=mediate,
                         admission_gate=pool_pressure_gate(tenant.pool), **kw)
    rec.engine = engine
    return engine, vmm


# ---------------------------------------------------------------------------
# The open-loop window
# ---------------------------------------------------------------------------


def chunk_lengths(prompts, chunk: int) -> List[int]:
    """Every chunk length the engine will run for these prompt lengths."""
    out = set()
    for p in prompts:
        out.add(min(chunk, p))
        if p > chunk and p % chunk:
            out.add(p % chunk)
    return sorted(out)


def warm_up(engine, params, vocab: int, lengths: List[int]):
    """Run each chunk shape and the decode step once, then clear stats."""
    from repro.serving.engine import EngineStats
    rng = np.random.default_rng(0)
    for n in lengths:
        engine.submit(rng.integers(0, vocab, size=n), max_new_tokens=2)
    while engine.has_work():
        engine.step(params)
    engine.stats = EngineStats()


class CompileCounter:
    """Counts traces and backend compiles from JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.n = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.n:
            self.n[event] += 1

    def total(self) -> int:
        return sum(self.n.values())


@dataclass
class Window:
    preroll: float
    seconds: float
    trace_s: float = 0.0

    @property
    def end(self) -> float:
        return self.preroll + self.seconds

    @property
    def host_end(self) -> float:
        """Where host timers stop counting: before the traced tail."""
        return self.end - self.trace_s


def serve(engine, params, requests: List[Request], win: Window,
          rec: Recorder, drain_s: float = 120.0, on_trace=None):
    """Drive the engine open-loop: each request is submitted once it is
    due (between steps, so a long step makes the next ones late; every
    latency counts from the due time). After the window no request
    arrives and the engine runs until every request has finished, at
    most ``drain_s`` seconds. ``on_trace(start: bool)`` is called at the
    start and the end of the traced tail. → dict of window-relative
    times and engine-stat snapshots."""
    by_rid = {}
    seen = {}
    i, n = 0, len(requests)
    snaps = {}
    traced = None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if "window" not in snaps and now >= win.preroll:
            snaps["window"] = dict(engine.stats.__dict__)
        if "host_end" not in snaps and now >= win.host_end:
            snaps["host_end"] = dict(engine.stats.__dict__)
        if on_trace and traced is None and now >= win.host_end \
                and win.trace_s > 0:
            on_trace(True)
            traced = [time.perf_counter() - t0, None]
        if "backlog" not in snaps and now >= win.end:
            snaps["backlog"] = (n - i) + len(engine.waiting) + sum(
                1 for x in engine.slots if x is not None)
        if traced and traced[1] is None and now >= win.end:
            traced[1] = time.perf_counter() - t0
            on_trace(False)
        with rec.span("bench.submit"):
            while i < n and requests[i].due <= now:
                r = requests[i]
                r.submitted = now
                by_rid[engine.submit(r.prompt, max_new_tokens=r.max_new)] = r
                i += 1
        if engine.has_work():
            step = rec.cur = Step(t0=now)
            with rec.span("bench.step"):
                finished = engine.step(params)
            t1 = time.perf_counter() - t0
            step.t1 = t1
            rec.cur = None
            rec.steps.append(step)
            live = [s for s in engine.slots if s is not None] + finished
            for r in live:
                k, prev = len(r.out_tokens), seen.get(r.rid, 0)
                if k > prev:
                    by_rid[r.rid].tokens_t.extend([t1] * (k - prev))
                    step.gen_tokens += k - prev
                    seen[r.rid] = k
            for r in finished:
                by_rid[r.rid].out = list(r.out_tokens)
        elif i < n:
            with rec.span("bench.wait"):
                time.sleep(max(0.0, min(requests[i].due - now, 0.005)))
        else:
            break
        if now > win.end + drain_s:
            break
    now = time.perf_counter() - t0
    if traced and traced[1] is None:
        traced[1] = now
        on_trace(False)
    for key in ("window", "host_end"):
        snaps.setdefault(key, dict(engine.stats.__dict__))
    snaps.setdefault("backlog", 0)
    return {"stats": snaps, "traced": traced, "end": now, "t0": t0}


# ---------------------------------------------------------------------------
# End-to-end metrics from the timestamps
# ---------------------------------------------------------------------------


def in_window(requests: List[Request], lo: float, hi: float):
    return [r for r in requests if lo <= r.due < hi]


def ttft_values(requests):
    """Due time to first token per request; a request that never got
    one is inf (it misses any limit)."""
    return [(r.tokens_t[0] - r.due) if r.tokens_t else math.inf
            for r in requests]


def itl_values(requests, lo: float, hi: float):
    """Gaps between consecutive output tokens whose later token came in
    ``[lo, hi)``."""
    out = []
    for r in requests:
        t = r.tokens_t
        out.extend(b - a for a, b in zip(t, t[1:]) if lo <= b < hi)
    return out


def tokens_per_s(steps: List[Step], lo: float, hi: float) -> float:
    """Prompt tokens prefilled plus tokens generated by the steps that
    ended in ``[lo, hi)``, over the window's seconds."""
    tok = sum(s.prefill_tokens + s.gen_tokens for s in steps
              if lo <= s.t1 < hi)
    return tok / (hi - lo)


def end_to_end(requests, steps, win: Window) -> dict:
    due = in_window(requests, win.preroll, win.end)
    gaps = itl_values(requests, win.preroll, win.end)
    return {
        "ttft_p95_ms": 1e3 * nearest_rank(ttft_values(due), 0.95),
        "itl_p95_ms": 1e3 * nearest_rank(gaps, 0.95),
        "itl_mean_ms": 1e3 * (sum(gaps) / len(gaps) if gaps else math.nan),
        "tokens_per_s": tokens_per_s(steps, win.preroll, win.end),
    }


# ---------------------------------------------------------------------------
# The check against the reference
# ---------------------------------------------------------------------------


def check_sample(requests: List[Request], n: int, seed: int) -> list:
    """The requests the check compares: the one with the most tokens,
    and others drawn from the seed, among those that finished."""
    done = [r for r in requests if r.out]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.out))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def _bucket(n: int, m: int) -> int:
    return -(-n // m) * m


def logit_gaps(params, cfg_data: dict, sample, controls=False,
               block: int = 512) -> dict:
    """The widest gap, over every served token of ``sample``, by which
    the reference's logit of the served token lies below its best
    (``program``); with ``controls``, the same gap of the token the
    float8 control puts first at each of those positions (``control``).
    The reference runs once per request over prompt plus served tokens,
    padded to a multiple of ``block`` (causal: padding changes nothing
    before it)."""
    import jax
    import jax.numpy as jnp
    from bench import reference

    mod = reference.load(cfg_data["reference"])
    fns = {"ref": reference.dot_f32, "ctl": reference.dot_fp8}
    jits = {k: jax.jit(lambda w, t, p, dot=dot: mod.logits(
        w, cfg_data, t, p, dot)) for k, dot in fns.items()}
    worst = {"program": 0.0, "control": 0.0}
    for r in sample:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        S = len(seq)
        pos = np.arange(len(r.prompt) - 1, S)
        tok = np.zeros(_bucket(S, block), np.int32)
        tok[:S] = seq
        posp = np.full(_bucket(len(pos), block), S - 1, np.int32)
        posp[:len(pos)] = pos
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jits["ref"](params, tok, posp))[:len(pos)]
            best = ref.max(-1)
            served = ref[np.arange(len(pos)), np.asarray(r.out)]
            worst["program"] = max(worst["program"],
                                   float((best - served).max()))
            if controls:
                ctl = np.asarray(jits["ctl"](params, tok, posp))[:len(pos)]
                pick = ref[np.arange(len(pos)), ctl.argmax(-1)]
                worst["control"] = max(worst["control"],
                                       float((best - pick).max()))
    return worst


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX found {len(devs)}")
    return devs


def log(msg: str):
    print(f"[bench] {msg}", flush=True)


def run_cell(args, t_start: float, manifest=None, require_tpu=True,
             fault=None, bench: Path = BENCH, workload=None,
             control: bool = False, cache: bool = True,
             check: bool = True) -> dict:
    """One run; → the result object. ``fault(engine)`` (tests only)
    breaks the timed path underneath before the window. ``control``
    also puts the float8 control in the program's place over the same
    prompts and positions and judges it by the same rule, under the
    result's ``control`` key (for the limit's upper reading; the
    benchmark's own runs do not)."""
    import jax
    from bench import configs, traffic, weights
    from repro.models import build_model

    wl = workload or load_workload(args.workload, bench)
    manifest = manifest if manifest is not None else load_manifest()
    e2e_names, layer_names = cell_metrics(manifest, args.workload)
    unit = units(manifest)
    if cache:
        CACHE.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        # no eviction, whatever the environment asks: eviction keeps an
        # access-time file beside each entry, and one entry without it
        # makes every later write to the cache fail
        jax.config.update("jax_compilation_cache_max_size", -1)
    devs = require_chips(wl["chips"]) if require_tpu else jax.devices()
    counter = CompileCounter()

    data = configs.load(wl["config"], bench / "configs")
    cfg = configs.model_config(data)
    model = build_model(cfg)
    params = weights.make(model, args.seed)
    jax.block_until_ready(params)

    rec = Recorder()
    engine, vmm = build_engine(cfg, model, wl, rec)
    tr = wl["traffic"]
    win = Window(preroll=float(tr.get("preroll_s", 0.0)),
                 seconds=float(args.seconds),
                 trace_s=float(wl.get("trace_s", 3.0)) if args.trace else 0.0)
    sched = traffic.generate_requests(
        tr, [(0.0, win.preroll), (win.preroll, win.end)], cfg.vocab,
        np.random.default_rng(args.seed))
    requests = [Request(t, p, o) for t, p, o in sched]
    warm_up(engine, params, cfg.vocab,
            chunk_lengths({len(r.prompt) for r in requests},
                          wl["engine"]["chunk_tokens"]))
    if fault is not None:
        fault(engine)
    rec.steps.clear()
    rec.vmm_calls.clear()
    compiles0 = counter.total()
    setup_s = time.perf_counter() - t_start

    trace_dir = RUNS / "trace" / args.workload
    tstate = {}

    def on_trace(start):
        if start:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(trace_dir))
            rec.tracing = True
            tstate["span"] = jax.profiler.TraceAnnotation(
                "bench.traced_window")
            tstate["span"].__enter__()
        else:
            tstate["span"].__exit__(None, None, None)
            rec.tracing = False
            jax.profiler.stop_trace()

    out = serve(engine, params, requests, win, rec,
                on_trace=on_trace if args.trace else None)
    compiles = counter.total() - compiles0
    due = in_window(requests, win.preroll, win.end)
    late = [r.submitted - r.due for r in requests if not math.isnan(r.submitted)]
    log(f"compiles inside the window: {compiles}")
    log(f"requests due in the window: {len(due)}; generator lateness "
        f"p50 {1e3 * nearest_rank(late, 0.5):.3f} ms, p99 "
        f"{1e3 * nearest_rank(late, 0.99):.3f} ms, max "
        f"{1e3 * max(late, default=0.0):.3f} ms")
    gaps = sorted(itl_values(requests, win.preroll, win.end))
    if gaps:
        mid = nearest_rank(gaps, 0.5)
        log(f"gaps between tokens in the window: {len(gaps)}; ms p50 "
            f"{1e3 * mid:.3f}, mean {1e3 * sum(gaps) / len(gaps):.3f}, p95 "
            f"{1e3 * nearest_rank(gaps, 0.95):.3f}, p99 "
            f"{1e3 * nearest_rank(gaps, 0.99):.3f}; share over 1.4 x p50 "
            f"{sum(g > 1.4 * mid for g in gaps) / len(gaps):.4f}")
    log(f"steps {len(rec.steps)}, drained at {out['end']:.2f} s "
        f"(window ends at {win.end:.2f} s); requests queued or in flight "
        f"at the window's end: {out['stats']['backlog']}")

    peak = 0
    for d in devs[:wl["chips"]]:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}

    metrics = {}
    breakdown = None
    if args.trace:
        from bench import trace as trace_mod
        trc = trace_mod.load(trace_mod.latest_xplane(str(trace_dir)))
        device["busy_s"] = trace_mod.busy_s(trc)
        device["window_s"] = trc.window_s
        breakdown = {"device_ops": trace_mod.top(trace_mod.op_seconds(trc)),
                     "idle_gaps": trace_mod.top(trace_mod.idle_gaps(trc))}
        run = RunView(data, wl, load_peaks(device["kind"], bench)
                      if require_tpu else _cpu_peaks(), rec.steps, requests,
                      win, out, trc, rec.vmm_calls)
        for name in layer_names:
            v = load_metric(name, bench).read(run)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": unit[name]}
    else:
        e2e = end_to_end(requests, rec.steps, win)
        e2e["setup_s"] = setup_s
        for name in e2e_names:
            metrics[name] = {"value": float(e2e[name]), "unit": unit[name]}

    # the check: state freed first, so the reference sets no peak above
    failed = sum(1 for r in due if not r.out)
    sample = check_sample(requests, int(wl["check"]["requests"]), args.seed)
    rec.engine = None
    engine.kv.state = None
    del engine
    if vmm is not None:
        vmm.shutdown()
    gc.collect()
    if not check:
        return {"correct": failed == 0, "attempted": len(due),
                "failed": failed, "metrics": metrics, "device": device,
                "backlog": out["stats"]["backlog"]}
    gaps = (logit_gaps(params, data, sample, controls=control) if sample
            else {"program": math.inf, "control": math.inf})
    limit = float(wl["check"]["max_logit_gap"])

    def checks_of(gap):
        return {"max_logit_gap": {"value": gap, "limit": limit}}
    checks = checks_of(gaps["program"])
    result = {"correct": verdict(checks, failed), "attempted": len(due),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        # the control in the program's place, judged by the same rule
        ctl = checks_of(gaps["control"])
        result["control"] = {"correct": verdict(ctl, failed), "checks": ctl}
    result["checks"] = checks
    return result


def verdict(checks: dict, failed: int) -> bool:
    """Correct: every request due in the window finished, and every
    number compared lies within its limit."""
    return failed == 0 and all(c["value"] <= c["limit"]
                               for c in checks.values())


def _cpu_peaks() -> dict:
    """Stand-in peaks for the CPU tests; never printed as a device's."""
    return {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11}


@dataclass
class RunView:
    """What a per-layer metric reader sees of one traced run."""
    cfg: dict
    workload: dict
    peaks: dict
    steps: List[Step]
    requests: List[Request]
    win: Window
    served: dict
    trace: object
    vmm_calls: list

    def host_steps(self) -> List[Step]:
        """Steps that ended in the untraced part of the window."""
        return [s for s in self.steps
                if self.win.preroll <= s.t1 < self.win.host_end]

    def traced_steps(self) -> List[Step]:
        lo, hi = self.served["traced"]
        return [s for s in self.steps if s.t0 >= lo and s.t1 <= hi]

    def stats_delta(self, key: str) -> int:
        s = self.served["stats"]
        return int(s["host_end"][key]) - int(s["window"][key])


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        result = run_cell(args, t_start)
    except NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
