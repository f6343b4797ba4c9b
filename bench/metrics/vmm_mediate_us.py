"""VMM data plane (core/vmm.py, core/scheduler.py): mean microseconds
of a traced ``vmm.run`` span not covered by the ``vmm.program`` span
inside it (``bench/spans.py``): the data plane's own cost per mediated
call, measured inside the VMM."""
from bench import spans


def read(run):
    return spans.mediate_us(run.trace)
