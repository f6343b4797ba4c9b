"""Program spans on the profiler's clock.

``span(name, **args)`` marks a stretch of host work as a named event in
the trace that ``jax.profiler`` is collecting, on the same clock as the
device's operations, so a device idle gap can be put down to the layer
the host was in. A running profiler is the only switch: with no trace
collecting, ``span`` returns one shared no-op context, builds nothing
and formats no argument.

Names are stable strings under four prefixes, one per layer:
``engine.`` (``serving/engine.py``), ``kv.`` (``serving/paged_kv.py``,
``paged_state.py``), ``mmu.`` (``core/mmu.py``) and ``vmm.``
(``core/vmm.py``). Spans nest by time on the thread that steps the
engine. A request's spans carry its ``rid``, the identifier the
``RequestTracer`` uses (the KV layer knows a request by its MMU owner,
``req<rid>``). Sites sit at layer boundaries; none opens once per slot
on every step.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A context that records ``name`` (with ``args`` as its stats) in
    the trace being collected, or a shared no-op when none is."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(name, **args)
