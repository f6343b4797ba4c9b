"""Reduction of the program's own spans (``repro.obs.span``) in a traced
run to what the per-layer metrics of the engine, the MMU and the VMM
read.

A program span is a host event named ``<layer>.<name>`` with one of the
layers ``engine``, ``kv``, ``mmu`` and ``vmm``; the benchmark's own
``bench.*`` spans, the profiler's Python frames and the runtime's
threads are not. Spans nest by time on the thread that steps the
engine, so the innermost span open at an instant is the shortest one
open then.

Idle gaps follow ``trace.idle_gaps``: gaps between the first device's
operations inside the traced window, those shorter than
``trace.SHORT_GAP_NS`` left out as the device's own. Each gap is put
down to the innermost program span open at its middle, or to ``NONE``.
Per-step figures divide by the ``engine.step`` spans that lie wholly
inside the window. All times are on the profiler's clock, in ns.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import trace

PROGRAM = re.compile(r"(engine|kv|mmu|vmm)\.[a-z_]+")
STEP = "engine.step"
NONE = "(no program span)"


def program_spans(tr: trace.Trace) -> List[Tuple[str, float, float]]:
    return [s for s in tr.host_spans if PROGRAM.fullmatch(s[0])]


def within(spans, window, name: str) -> list:
    """The spans called ``name`` that lie wholly inside ``window``."""
    lo, hi = window
    return [s for s in spans if s[0] == name and lo <= s[1] and s[2] <= hi]


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    tot, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        if e > end:
            tot += e - max(s, end)
            end = e
    return tot


def gaps(tr: trace.Trace) -> List[Tuple[float, float]]:
    """Idle gaps of the first device inside the window, short ones out."""
    if not tr.device_ops:
        return []
    ops = tr.device_ops[sorted(tr.device_ops)[0]]
    lo, hi = tr.window
    edges = [lo] + [x for iv in trace.busy_intervals(ops, tr.window)
                    for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2])
            if b - a >= trace.SHORT_GAP_NS]


def innermost(spans, times) -> List[Optional[str]]:
    """The innermost of ``spans`` open at each of ``times``, or None."""
    starts = np.array([s[1] for s in spans], np.float64)
    ends = np.array([s[2] for s in spans], np.float64)
    out = []
    for t in times:
        open_ = np.nonzero((starts <= t) & (ends > t))[0]
        out.append(spans[open_[np.argmin(ends[open_] - starts[open_])]][0]
                   if len(open_) else None)
    return out


def idle_by_span(tr: trace.Trace) -> Dict[str, float]:
    """Idle device seconds by the innermost program span open at the
    middle of each gap; ``NONE`` where no program span is open."""
    gs = gaps(tr)
    out: Dict[str, float] = {}
    names = innermost(program_spans(tr), [(a + b) / 2 for a, b in gs])
    for (a, b), name in zip(gs, names):
        key = name or NONE
        out[key] = out.get(key, 0.0) + (b - a) * 1e-9
    return out


def steps(tr: trace.Trace) -> int:
    return len(within(program_spans(tr), tr.window, STEP))


def idle_ms_per_step(tr: trace.Trace, prefixes) -> Optional[float]:
    """Idle device ms per traced engine step in the gaps whose innermost
    program span starts with one of ``prefixes``; None where the trace
    holds no device operation or no whole engine step."""
    n = steps(tr)
    if not n or not tr.device_ops:
        return None
    idle = idle_by_span(tr)
    return 1e3 * sum(v for k, v in idle.items()
                     if k.startswith(tuple(prefixes))) / n


def mediate_us(tr: trace.Trace) -> Optional[float]:
    """Mean over the ``vmm.run`` spans inside the window of their
    duration less the part ``vmm.program`` spans cover, in us."""
    sp = program_spans(tr)
    runs = within(sp, tr.window, "vmm.run")
    if not runs:
        return None
    progs = [(s[1], s[2]) for s in sp if s[0] == "vmm.program"]
    tot = 0.0
    for _, a, b in runs:
        inner = [(max(s, a), min(e, b)) for s, e in progs if s < b and e > a]
        tot += (b - a) - union_ns(inner)
    return tot / len(runs) * 1e-3


def coverage(tr: trace.Trace) -> Dict[str, float]:
    """Idle seconds in all, in gaps whose middle lies inside an
    ``engine.step``, and of those the part no span finer than the step
    names; idle seconds no program span names."""
    sp = program_spans(tr)
    gs = gaps(tr)
    mids = [(a + b) / 2 for a, b in gs]
    names = innermost(sp, mids)
    in_step = innermost([s for s in sp if s[0] == STEP], mids)
    out = {"idle_s": 0.0, "in_step_s": 0.0, "bare_step_s": 0.0,
           "none_s": 0.0}
    for (a, b), name, st in zip(gs, names, in_step):
        d = (b - a) * 1e-9
        out["idle_s"] += d
        out["none_s"] += d if name is None else 0.0
        if st is not None:
            out["in_step_s"] += d
            out["bare_step_s"] += d if name == STEP else 0.0
    return out
