"""Per-layer metric readers, one file each, named as in BENCHMARK.json.

Each file holds ``read(run) -> float | None`` over a
:class:`bench.harness.RunView` of a traced run. A reader that finds
nothing to read returns None and the metric is left out of the line.
The helper below is shared by the kernels' readers.
"""
from __future__ import annotations

from bench import flops


def kernel_roofline(run, pattern: str, cost_of_step):
    """Share of a kernel's roofline in %: the least time its calls in
    the traced steps could take, over the device time of the trace's
    operations matching ``pattern``. ``cost_of_step(step)`` lists the
    (FLOPs, bytes) of each call the step made."""
    from bench import trace
    t = trace.kernel_seconds(run.trace, pattern)
    if t <= 0:
        return None
    pf = run.peaks["flops_per_s"]["bfloat16"]
    bw = run.peaks["hbm_bytes_per_s"]
    least = sum(flops.roofline_s(f, b, pf, bw)
                for s in run.traced_steps() for f, b in cost_of_step(s))
    return 100.0 * least / t if least > 0 else None
