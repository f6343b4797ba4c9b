"""Fault tolerance + elasticity: train under the VMM, lose the slice,
migrate, resume from the tenant checkpoint, then grow the slice
(resource-elastic virtualization).

Run:  PYTHONPATH=src python examples/elastic_failover.py
"""
import os
if os.environ.get("JAX_PLATFORMS") == "cpu":      # simulate an 8-chip pod
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import tempfile                                   # noqa: E402
import numpy as np                                # noqa: E402
import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402

from repro import optim                           # noqa: E402
from repro.configs import get_config              # noqa: E402
from repro.configs.base import ShapeCell          # noqa: E402
from repro.core import VMM, ProgramRequest        # noqa: E402
from repro.core import elastic                    # noqa: E402
from repro.data import pipeline_for               # noqa: E402
from repro.launch.mesh import make_local_mesh     # noqa: E402
from repro.models import build_model              # noqa: E402

ARCH = "internlm2-1.8b"
half = len(jax.devices()) // 2                    # 4 on the CPU, 2 on 4 chips
mesh = make_local_mesh((2, half))
vmm = VMM(mesh, policy="hybrid", ckpt_root=tempfile.mkdtemp())
tenant = vmm.create_vm("trainer", (1, half))
tenant.device.open()

cfg = get_config(ARCH, reduced=True)
cell = ShapeCell("ef", 64, 4, "train")
model = build_model(cfg)
oc = optim.OptConfig(warmup_steps=2, decay_steps=30)
pipe = pipeline_for(cfg, cell)

req = ProgramRequest(arch=ARCH, kind="train", seq_len=64, global_batch=4)
tenant.device.reprogram(req)

params = model.init(jax.random.PRNGKey(0))
opt_state = optim.init(oc, params)

events = []
tenant.device.set_status(lambda ev: events.append(ev.kind))

for step in range(6):
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
    params, opt_state, m = tenant.device.run(params, opt_state, batch)
print(f"[phase1] 6 steps on slice {tenant.vslice.spec.origin}, "
      f"loss={float(m['loss']):.4f}")

# checkpoint tenant state, then lose the slice
tenant.state = {"params": params, "opt": opt_state}
vmm.checkpoint_tenant(tenant)
vmm.mark_slice_failed(tenant.vslice.slice_id)
print(f"[failure] slice marked failed, events={events}")

# migrate to a fresh equal slice; state restored from checkpoint
template = {"params": jax.tree.map(jnp.zeros_like, params),
            "opt": jax.tree.map(jnp.zeros_like, opt_state)}
vmm.migrate_tenant(tenant, new_shape=(1, half), state_template=template)
params, opt_state = tenant.state["params"], tenant.state["opt"]
print(f"[migrated] now on slice {tenant.vslice.spec.origin} "
      f"(healthy={tenant.vslice.healthy})")

for step in range(6, 12):
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
    params, opt_state, m = tenant.device.run(params, opt_state, batch)
print(f"[phase2] resumed, loss={float(m['loss']):.4f}")

# elastic grow: the whole pod
tenant.state = {"params": params, "opt": opt_state}
elastic.resize(vmm, tenant, (2, half), state_template=template)
params, opt_state = tenant.state["params"], tenant.state["opt"]
print(f"[elastic] grown to {tenant.vslice.spec.shape} = "
      f"{tenant.vslice.n_devices} chips")
for step in range(12, 18):
    batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
    params, opt_state, m = tenant.device.run(params, opt_state, batch)
print(f"[phase3] on grown slice, loss={float(m['loss']):.4f}")
print("vmm stats:", vmm.stats())
vmm.shutdown()
