"""MMU and paged memory (core/mmu.py, serving/paged_kv.py,
paged_state.py): device idle milliseconds per traced engine step in the
gaps whose innermost program span is ``kv.*`` or ``mmu.*``
(``bench/spans.py``): what leasing, growing and freeing pages costs the
chip."""
from bench import spans


def read(run):
    return spans.idle_ms_per_step(run.trace, ("kv.", "mmu."))
