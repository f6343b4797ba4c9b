"""Serving engine (serving/engine.py): mean wall milliseconds of the
``engine.step`` calls that decode, from the benchmark's timer; steps in
the untraced part of the window."""


def read(run):
    d = [s.t1 - s.t0 for s in run.host_steps() if s.lengths is not None]
    return 1e3 * sum(d) / len(d) if d else None
