"""Paper Fig. 6b — breakdown of virtualized vector-add time.

The paper decomposes vFPGA vecadd into software computation (~55%),
data transfer and kernel time. vPOD's decomposition: guest-copy (VM-copy
staging), DMA (device_put), MMU (translate/alloc), scheduling+logging
(VMM mediation), and device compute.

Attribution comes from the telemetry plane, not private timers: the
benchmark drives the mediated ops and then *reads* what the stack
already recorded — ``TransferEngine`` stage counters,
``VMM.stats()["ops"]`` per-op latency from the OpLog's ``perf_counter``
stamps, and the MMU's ``mmu_translate_s``/``mmu_alloc_s`` histograms in
the obs registry. Only the end-to-end total is timed here.
"""
from __future__ import annotations

import tempfile
import time

import jax
import numpy as np


def run():
    from jax.sharding import Mesh
    from repro.core import VMM
    from repro.kernels.vecadd.ops import vecadd_op
    from repro.obs import ObsHub

    N = 1 << 20
    rng = np.random.default_rng(0)
    x = rng.standard_normal(N).astype(np.float32)
    y = rng.standard_normal(N).astype(np.float32)

    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    vmm = VMM(Mesh(devs, ("data", "model")), policy="hybrid",
              hbm_per_chip=1 << 30, ckpt_root=tempfile.mkdtemp(),
              obs=ObsHub(enabled=True))
    t = vmm.create_vm("bench", (1, 1))
    dev = t.device
    dev.open()
    # block inside the program so the op log's "run" records cover the
    # device compute, not just dispatch
    t.program = lambda ab: jax.block_until_ready(vecadd_op(ab[0], ab[1]))

    iters = 10
    h = dev.alloc(x.nbytes + y.nbytes, (2, N), "float32")
    xy = np.stack([x, y])
    # warmup (compile)
    dev.write(h, xy)
    dev.run((jax.numpy.asarray(x), jax.numpy.asarray(y)))
    vmm.transfer.stats.__init__()
    reg = vmm.obs.registry
    n_runs0 = len(vmm.oplog.query(op="run"))   # skip warmup records

    t0_all = time.perf_counter_ns()
    for _ in range(iters):
        t.pool.translate(h, owner="bench")    # → mmu_translate_s histogram
        dev.write(h, xy)                      # → transfer stage counters
        dx, dy = jax.numpy.asarray(x), jax.numpy.asarray(y)
        dev.run((dx, dy))                     # → oplog "run" records
    total_ns = time.perf_counter_ns() - t0_all

    # --- read the registry instead of re-measuring ---------------------
    ts = vmm.transfer.stats
    guest_copy = ts.guest_copy_ns / iters
    dma = ts.dma_ns / iters
    mmu = 1e9 * sum(reg.histogram(name).summary()["mean"]
                    for name in ("mmu_translate_s", "mmu_alloc_s"))
    ops = vmm.stats()["ops"]
    # the warmup run is in the log too — average only the measured iters
    measured = [r.duration_ms for r in vmm.oplog.query(op="run")[n_runs0:]]
    compute = (np.mean(measured) if measured
               else ops["run"]["mean_ms"]) * 1e6
    total = total_ns / iters
    sched = max(total - guest_copy - dma - mmu - compute, 0.0)

    rows = [("fig6b.guest_copy", guest_copy / 1e3,
             f"{guest_copy / total:.1%}"),
            ("fig6b.dma", dma / 1e3, f"{dma / total:.1%}"),
            ("fig6b.mmu", mmu / 1e3, f"{mmu / total:.1%}"),
            ("fig6b.compute+run", compute / 1e3, f"{compute / total:.1%}"),
            ("fig6b.sched_log_other", sched / 1e3, f"{sched / total:.1%}"),
            ("fig6b.total", total / 1e3, "100%")]
    software = (guest_copy + mmu + sched) / total
    rows.append(("fig6b.software_fraction", software * 100,
                 f"paper measured ~55% on vFPGA"))
    rows.append(("fig6b.run_p95_ms", ops["run"]["p95_ms"],
                 "from VMM.stats()['ops'] (OpLog percentiles)"))
    vmm.shutdown()
    return rows
