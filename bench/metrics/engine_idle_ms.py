"""Serving engine (serving/engine.py): device idle milliseconds per
traced engine step in the gaps whose innermost program span is
``engine.*`` (``bench/spans.py``): admission, chunk scheduling, the
block-table copy, row resets, the blocking fetch and the emission."""
from bench import spans


def read(run):
    return spans.idle_ms_per_step(run.trace, ("engine.",))
