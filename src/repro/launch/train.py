"""End-to-end training driver (CPU-runnable; same code path as a pod).

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 50 --batch 8 --seq 128
    PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
        --steps 20 --virtualized          # run through the VMM (hybrid)
    ... --fail-at 10 --resume             # simulated failure + restart

The ``--virtualized`` path drives the identical train step through the
VMM's reprogram/run operators (the paper's fidelity claim: same flow,
mediated control plane), with periodic tenant checkpoints (interposition).
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    """Train as ``argv`` says; returns the loss of each step it ran."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full (paper-dims) config instead of reduced")
    ap.add_argument("--virtualized", action="store_true")
    ap.add_argument("--policy", default="hybrid",
                    choices=["fev", "bev", "hybrid"])
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "vpod_train_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=0,
                    help="simulate a crash at this step (test restart)")
    ap.add_argument("--micro-steps", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    args = ap.parse_args(argv)

    from repro import optim
    from repro.checkpointing import CheckpointManager
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.data import pipeline_for
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model
    from repro.parallel import build_train

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=not args.full)
    cell = ShapeCell("cli", args.seq, args.batch, "train")
    mesh = make_local_mesh((1, len(jax.devices())))
    model = build_model(cfg)
    opt_cfg = optim.OptConfig(warmup_steps=5, decay_steps=max(args.steps, 10),
                              micro_steps=args.micro_steps,
                              grad_compress=args.grad_compress,
                              state_dtype=cfg.opt_dtype)

    pipe = pipeline_for(cfg, cell, seed=0)
    mgr = CheckpointManager(args.ckpt_dir, save_interval=args.ckpt_every,
                            keep_n=2)

    params = model.init(jax.random.PRNGKey(0))
    opt_state = optim.init(opt_cfg, params)
    start_step = 0
    if args.resume:
        restored = mgr.restore_latest({"params": params, "opt": opt_state})
        if restored is not None:
            start_step, tree, _ = restored
            params, opt_state = tree["params"], tree["opt"]
            print(f"[train] resumed from step {start_step}")

    if args.virtualized:
        from repro.core import VMM, ProgramRequest
        devs = np.array(jax.devices()[:1]).reshape(1, 1)
        from jax.sharding import Mesh
        vmm = VMM(Mesh(devs, ("data", "model")), policy=args.policy,
                  ckpt_root=args.ckpt_dir + "_vmm")
        tenant = vmm.create_vm("trainer", (1, 1))
        tenant.device.open()
        req = ProgramRequest(arch=args.arch, kind="train",
                             seq_len=args.seq, global_batch=args.batch,
                             reduced=not args.full)
        tenant.device.reprogram(req)
        run = lambda p, o, b: tenant.device.run(p, o, b)  # noqa: E731
    else:
        jitted, _ = build_train(cfg, mesh, cell, opt_cfg)
        run = jitted

    t_start = time.perf_counter()
    losses = []
    for step in range(start_step, args.steps):
        if args.fail_at and step == args.fail_at:
            print(f"[train] simulated failure at step {step} — restart "
                  f"with --resume")
            raise SystemExit(17)
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
        t0 = time.perf_counter()
        params, opt_state, metrics = run(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = time.perf_counter() - t0
        if step % 5 == 0 or step == args.steps - 1:
            print(f"[train] step={step:4d} loss={loss:8.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):7.3f} "
                  f"dt={dt*1e3:7.1f}ms")
        if mgr.should_save(step):
            mgr.save(step, {"params": params, "opt": opt_state},
                     meta={"arch": args.arch})
    mgr.wait()
    total = time.perf_counter() - t_start
    print(f"[train] done: {args.steps - start_step} steps in {total:.1f}s")
    if args.virtualized:
        tenant.state = {"params": params, "opt": opt_state}
        vmm.checkpoint_tenant(tenant)
        print("[train] vmm stats:", vmm.stats())
        vmm.shutdown()
    return losses


if __name__ == "__main__":
    main()
