"""Multi-tenant serving — the paper's Figure-2 cloud scenario.

A pod of N devices (four TPU chips, or eight host-platform devices on
the CPU) is floorplanned as a (2, N/2) mesh into two (1, N/2) vSlices;
two tenants serve different architectures concurrently, each
through its own GuestDevice, with the data plane mediated by the
weighted-fair-queueing scheduler (alice weight 3, bob weight 1) and the
decode loops driven through the async ``run_async`` futures API.
Includes the paper's cross-PRR reprogram attack (denied + audited), a
warm-reconfiguration cache hit, and the per-tenant scheduler stats.

Run:  PYTHONPATH=src python examples/multi_tenant_serving.py
      ... --policy slo   # deadline-scheduled data plane: alice serves
      # a latency-sensitive class (PRIORITY_HIGH, 50 ms wait budget),
      # bob batch traffic — stats report per-tenant SLO attainment
"""
import os
if os.environ.get("JAX_PLATFORMS") == "cpu":      # simulate an 8-chip pod
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import argparse                                   # noqa: E402
import tempfile                                   # noqa: E402
import numpy as np                                # noqa: E402
import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402

from repro.core import (VMM, LegalityError, PRIORITY_HIGH,  # noqa: E402
                        ProgramRequest, report)
from repro.launch.mesh import make_local_mesh     # noqa: E402
from repro.obs import ObsHub                      # noqa: E402

ap = argparse.ArgumentParser()
ap.add_argument("--policy", default="wfq", choices=["wfq", "slo"])
ap.add_argument("--metrics", action="store_true",
                help="enable the telemetry plane and print the "
                     "Prometheus exposition at exit")
cli = ap.parse_args()

half = len(jax.devices()) // 2
mesh = make_local_mesh((2, half))
vmm = VMM(mesh, policy=cli.policy, ckpt_root=tempfile.mkdtemp(),
          obs=ObsHub(enabled=cli.metrics))

if cli.policy == "slo":
    # deadline classes instead of weights: alice is latency-sensitive
    alice = vmm.create_vm("alice", (1, half),
                          sched_priority=PRIORITY_HIGH,
                          sched_slo_wait_s=0.05)
    bob = vmm.create_vm("bob", (1, half))
else:
    alice = vmm.create_vm("alice", (1, half), sched_weight=3.0)
    bob = vmm.create_vm("bob", (1, half), sched_weight=1.0)
print("floorplan:", vmm.floorplanner.snapshot())

for tenant, arch in ((alice, "qwen1.5-0.5b"), (bob, "internlm2-1.8b")):
    tenant.device.open()
    req = ProgramRequest(arch=arch, kind="decode", seq_len=64,
                         global_batch=4)
    prog = tenant.device.reprogram(req)
    args = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        prog.bitfile.abstract_args)
    token = jnp.ones((4, 1), jnp.int32)
    logits, caches = tenant.device.run(args[0], args[1], token,
                                       jnp.int32(0))
    for pos in range(1, 6):   # short decode loop, async submission
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        fut = tenant.device.run_async(args[0], caches, nxt,
                                      jnp.int32(pos))
        logits, caches = fut.result(timeout=60)
    print(f"[{tenant.name}] served 6 tokens of {arch}; "
          f"logits {logits.shape}")

# --- the paper's isolation attack: alice flashes bob's slice -------------
try:
    stolen_bitfile = alice.program.bitfile
    bob.device.reprogram(stolen_bitfile)          # bound to alice's slice!
except LegalityError as e:
    print(f"[isolation] cross-slice reprogram denied: {e}")

# --- warm reconfiguration (same topology class) ---------------------------
alice.device.reprogram(ProgramRequest(arch="qwen1.5-0.5b", kind="decode",
                                      seq_len=64, global_batch=4))
print(f"compile cache: hits={vmm.compiler.hits} "
      f"misses={vmm.compiler.misses}")
sched = vmm.stats()["scheduler"]
for name, s in sched["tenants"].items():
    line = (f"[sched:{sched['policy']}] {name}: weight={s['weight']} "
            f"completed={s['completed']} avg_wait={s['avg_wait_ms']:.2f}ms "
            f"avg_service={s['avg_service_ms']:.2f}ms")
    if "slo_attainment" in s:
        line += (f" slo_budget={s['slo_wait_ms']:.0f}ms "
                 f"attainment={s['slo_attainment']:.0%} "
                 f"p95_wait={s['p95_wait_ms']:.2f}ms")
    print(line)
print(report(vmm).to_markdown())
if cli.metrics:
    print("[obs] prometheus exposition:")
    print(vmm.obs.prometheus())
vmm.shutdown()
