"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, time per device operation,
and the idle gaps between operations named by the host span open
across each gap (the benchmark's own spans, the profiler's Python
frames, and the runtime's threads).

The trace is read with ``jax.profiler.ProfileData``. Device planes are
those named ``/device:TPU:<n>`` (a ``TPU_NON_CORE`` plane is skipped);
their operations are the events of the line ``XLA Ops``. Host spans are
the events of the host plane's threads. The traced window is the host
span ``WINDOW_SPAN``, which the harness opens around the traced steps.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

WINDOW_SPAN = "bench.traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    """Events as (name, start_ns, end_ns), on one clock."""
    window: Tuple[float, float]
    device_ops: Dict[str, List[Tuple[str, float, float]]]  # per device
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
    return from_events(devices, host)


def from_events(devices, host) -> Trace:
    """A Trace from raw events; the window is the ``WINDOW_SPAN`` host
    span, or the extent of the device events where there is none."""
    win = [s for s in host if s[0] == WINDOW_SPAN]
    if win:
        window = (win[0][1], win[0][2])
    else:
        ev = [e for ops in devices.values() for e in ops]
        if not ev:
            raise ValueError("trace holds no window span and no device op")
        window = (min(e[1] for e in ev), max(e[2] for e in ev))
    return Trace(window, devices, [s for s in host if s[0] != WINDOW_SPAN])


def _clip(events, window):
    lo, hi = window
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def busy_intervals(ops, window) -> List[Tuple[float, float]]:
    """Union of the op intervals inside the window, sorted."""
    out = []
    for _, s, e in sorted(_clip(ops, window), key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_s(tr: Trace) -> float:
    """Seconds with an operation running, averaged over the devices."""
    if not tr.device_ops:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(ops, tr.window))
              for ops in tr.device_ops.values())
    return tot * 1e-9 / len(tr.device_ops)


#: an op whose event spans the ops of its body (a scan's loop, a call)
CONTAINER = re.compile(r"\) (while|conditional|call)\(")
#: how much of an op's HLO text names it in the breakdown
NAME_CHARS = 160


def op_seconds(tr: Trace) -> Dict[str, float]:
    """Device seconds per operation, summed over devices. Events are
    named by their HLO text, cut to ``NAME_CHARS``; loop and call ops,
    whose events cover their bodies' ops, are left out."""
    out: Dict[str, float] = {}
    for ops in tr.device_ops.values():
        for name, s, e in _clip(ops, tr.window):
            if CONTAINER.search(name):
                continue
            key = name[:NAME_CHARS]
            out[key] = out.get(key, 0.0) + (e - s) * 1e-9
    return out


def kernel_seconds(tr: Trace, pattern: str) -> float:
    """Device seconds of the operations whose HLO name (the text before
    `` = ``) matches ``pattern``."""
    rx = re.compile(pattern)
    tot = 0.0
    for ops in tr.device_ops.values():
        for name, s, e in _clip(ops, tr.window):
            if rx.search(name.split(" = ", 1)[0]):
                tot += (e - s) * 1e-9
    return tot


#: gaps shorter than this are the device's own launch gaps, not the host's
SHORT_GAP_NS = 20_000
SHORT_GAP = "(gaps under 20 us)"


def idle_gaps(tr: Trace) -> Dict[str, float]:
    """Idle device seconds (on the first device) by the innermost host
    span open at the middle of each gap; ``(none)`` where none is, and
    ``SHORT_GAP`` for the many gaps between back-to-back operations."""
    if not tr.device_ops:
        return {}
    ops = tr.device_ops[sorted(tr.device_ops)[0]]
    busy = busy_intervals(ops, tr.window)
    lo, hi = tr.window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = [sp for sp in tr.host_spans if sp[2] > lo and sp[1] < hi]
    starts = np.array([sp[1] for sp in spans], np.float64)
    ends = np.array([sp[2] for sp in spans], np.float64)
    out: Dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        if b - a < SHORT_GAP_NS:
            out[SHORT_GAP] = out.get(SHORT_GAP, 0.0) + (b - a) * 1e-9
            continue
        mid = (a + b) / 2
        open_ = np.nonzero((starts <= mid) & (ends > mid))[0]
        name = (spans[open_[np.argmin(ends[open_] - starts[open_])]][0]
                if len(open_) else "(none)")
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
