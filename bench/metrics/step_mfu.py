"""Model step (models/): model FLOPs of every program the window's
engine steps ran (prefill chunks and decode, attention included), over
the steps' wall time, over the chip's bf16 peak, in %. Steps in the
untraced part of the window."""
from bench import flops


def read(run):
    steps = run.host_steps()
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0:
        return None
    f = 0.0
    for s in steps:
        for length, start in s.chunks:
            f += flops.chunk_flops(run.cfg, length, int(start))
        if s.lengths is not None and len(s.lengths):
            f += flops.decode_flops(run.cfg, s.lengths)
    return 100.0 * f / wall / run.peaks["flops_per_s"]["bfloat16"]
