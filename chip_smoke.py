"""Bring-up check on a TPU: the serving path at full width, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the VMM's multi-tenant path, 4 chips

With no option it runs, on ``qwen1.5-0.5b`` at its published width with
random weights from ``--seed``:

1. ``serve``: ``repro.launch.serve --full`` on the chunked/fused path, on
   the default monolithic path, and on the chunked path through the VMM
   (``--virtualized``). Every request finishes, no admission re-prefills
   (``full_prefills == 0``), and the compiled fused decode step holds the
   Pallas kernels (``tpu_custom_call``).
2. ``kernels-vs-xla``: greedy decoding on the kernel path, replayed
   token for token on the XLA path. Logits agree within ``LOGIT_TOL`` of
   the largest logit, and the on-device sampler returns the argmax.
3. ``train``: a few steps of ``repro.launch.train`` at reduced width.

``--chips 4`` runs only the multi-chip path: a VMM over the four chips as
a (2, 2) mesh, two tenants on disjoint (1, 2) slices each reprogramming a
full-width decode program, the same program on a one-chip slice for
comparison, and the paper's cross-slice reprogram attack.

It fails (nonzero exit, no result line) when JAX finds no TPU or any phase
fails. The last line of its output is the result as JSON.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "qwen1.5-0.5b"
#: Two bfloat16 programs that round at different places agree to
#: |Δlogit| ≤ LOGIT_TOL · max|logit|: ten bf16 roundings (2^-8 each). The
#: attention kernels order their softmax sums unlike XLA, and a sharded
#: program adds bf16 partial products across chips; over 24 layers the
#: logits drift 1.5-2e-2 apart. What is compared is pinned exactly on the
#: CPU: each kernel against its fp32 oracle (tests/test_kernels.py), the
#: sharded programs against one device in fp32 (tests/test_integration.py).
LOGIT_TOL = 10 * 2.0 ** -8


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, what):
    """Fail the run (``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def close(got, want, vocab):
    """Max |got - want| over max |want| on the first ``vocab`` logits
    (the padded tail holds -1e30 masks), as float."""
    import numpy as np
    got = np.asarray(got, np.float32)[..., :vocab]
    want = np.asarray(want, np.float32)[..., :vocab]
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def phase_serve():
    """Three ``launch.serve`` runs; returns nothing, raises on a fault."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve

    n_req = 6
    base = ["--requests", str(n_req), "--batch", "4", "--prompt-len", "24",
            "--max-new", "8", "--capacity", "128", "--full"]
    runs = {"chunked": base + ["--chunk-tokens", "16"],
            "monolithic": base,
            "virtualized": base + ["--chunk-tokens", "16", "--virtualized"]}
    for name, argv in runs.items():
        t0 = time.perf_counter()
        out = serve.main(argv)
        eng, done = out["engine"], out["finished"]
        s = eng.stats
        check(len(done) == n_req, f"{name}: {len(done)}/{n_req} finished")
        check(all(len(r.out_tokens) == r.max_new_tokens for r in done),
              f"{name}: a request stopped short of its token budget")
        check(s.full_prefills == 0, f"{name}: {s.full_prefills} re-prefills")
        log(f"serve[{name}]: {len(done)} requests, "
            f"{s.generated_tokens} tokens, {s.steps} steps, "
            f"{out['seconds']:.2f} s serving, "
            f"{time.perf_counter() - t0:.2f} s with compiles")
        if name == "chunked":
            B, i32 = eng.B, jnp.int32
            step = jax.jit(out["model"].decode_paged_fused,
                           donate_argnums=(1,))
            text = step.lower(
                out["params"], eng.kv.state, jnp.zeros((B, 1), i32),
                jnp.zeros((B,), i32), jnp.asarray(eng.kv.block_tables()),
                jnp.zeros((B,), jnp.float32), i32(0)).compile().as_text()
            n = text.count("tpu_custom_call")
            log(f"serve[{name}]: compiled fused decode step holds {n} "
                f"tpu_custom_call")
            check(n >= 2, "kernels missing from decode step")
        del out, eng


def phase_kernels_vs_xla(seed, B=4, L=32, steps=8, ps=16):
    """Greedy tokens of the kernel path, replayed on the XLA path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.kernels.common import kernel_path
    from repro.kernels.decode_attention.ops import sample_tokens_op
    from repro.models import build_model

    cfg = get_config(ARCH)
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    prompt = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (B, L)), jnp.int32)
    nb = -(-(L + steps) // ps)
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    i32 = jnp.int32

    def run(pallas, feed=None):
        """→ (prefill logits, per-step decode logits, greedy tokens)."""
        with kernel_path(pallas):
            model = build_model(cfg)
            prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[0])
            chunk = jax.jit(model.prefill_chunk_paged, donate_argnums=(1,))
            decode = jax.jit(model.decode_paged, donate_argnums=(1,))
            first = prefill(params, prompt)
            state = model.init_paged_state(B, B * nb, ps)
            hlo = decode.lower(params, state, prompt[:, :1],
                               jnp.full((B,), L, i32), bt).compile()
            kernels = hlo.as_text().count("tpu_custom_call")
            for b in range(B):
                _, state = chunk(params, state, prompt[b:b + 1], i32(b),
                                 bt[b], i32(0))
            tok = (sample_tokens_op(first, jnp.zeros((B,)),
                                    jnp.zeros(first.shape))
                   if feed is None else feed[:, 0])
            logits, toks = [], [tok]
            for t in range(steps):
                lg, state = decode(params, state, tok[:, None],
                                   jnp.full((B,), L + t, i32), bt)
                logits.append(lg)
                if feed is None:
                    tok = sample_tokens_op(lg, jnp.zeros((B,)),
                                           jnp.zeros(lg.shape))
                    check(np.array_equal(
                        np.asarray(tok),
                        np.argmax(np.asarray(lg, np.float32), -1)),
                        f"sampler is not the argmax at step {t}")
                else:
                    tok = feed[:, t + 1]
                toks.append(tok)
            return first, jnp.stack(logits), jnp.stack(toks, 1), kernels

    t0 = time.perf_counter()
    first_k, logits_k, toks_k, n_k = run(True)
    first_x, logits_x, _, n_x = run(False, feed=toks_k)
    log(f"kernels-vs-xla: decode program holds {n_k} tpu_custom_call on "
        f"the kernel path, {n_x} on the XLA path")
    check(n_x == 0 and n_k > 0,
          f"kernel path holds {n_k} kernels, XLA path {n_x}")
    err_prefill = close(first_k, first_x, cfg.vocab)
    err_decode = close(logits_k, logits_x, cfg.vocab)
    agree = float(np.mean(np.argmax(np.asarray(logits_k, np.float32), -1)
                          == np.argmax(np.asarray(logits_x, np.float32),
                                       -1)))
    log(f"kernels-vs-xla: prefill |Δ|/max = {err_prefill:.3e}, decode "
        f"|Δ|/max = {err_decode:.3e} (tol {LOGIT_TOL:.3e}), greedy argmax "
        f"agreement {agree:.3f} over {B}x{steps} tokens, "
        f"{time.perf_counter() - t0:.2f} s")
    check(max(err_prefill, err_decode) <= LOGIT_TOL,
          "kernel and XLA logits disagree")


def phase_train():
    from repro.launch import train

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as ckpt:
        losses = train.main(["--steps", "4", "--batch", "8", "--seq", "128",
                             "--ckpt-dir", ckpt, "--ckpt-every", "100"])
    import math
    check(len(losses) == 4 and all(math.isfinite(x) for x in losses),
          f"train losses {losses}")
    log(f"train: losses {[round(x, 4) for x in losses]}, "
        f"{time.perf_counter() - t0:.2f} s")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def phase_four_chips(seed, B=4, steps=6):
    """Two tenants on disjoint (1, 2) slices of a (2, 2) pod, then one
    tenant on a (1, 1) slice, all decoding the same tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core import VMM, LegalityError, ProgramRequest
    from repro.models import build_model

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 found {len(devs)} devices")
    cfg = get_config(ARCH)
    req = ProgramRequest(ARCH, "decode", seq_len=64, global_batch=B,
                         reduced=False)
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    feed = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (B, steps)), jnp.int32)

    def decode(tenant):
        """Reprogram, decode ``steps`` fed tokens; check placement."""
        prog = tenant.device.reprogram(req)
        p_sh, c_sh = prog.bitfile.compiled.input_shardings[0][:2]
        p_abs, c_abs = prog.bitfile.abstract_args[:2]
        p = jax.device_put(jax.tree.map(lambda x, a: x.astype(a.dtype),
                                        params, p_abs), p_sh)
        caches = jax.device_put(jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype), c_abs), c_sh)
        want = set(tenant.vslice.devices.flat)
        logits = []
        for t in range(steps):
            lg, caches = tenant.device.run(p, caches, feed[:, t:t + 1],
                                           jnp.int32(t))
            for leaf in [lg] + jax.tree.leaves(caches):
                check(set(leaf.sharding.device_set) == want,
                      f"{tenant.name}: output on {leaf.sharding} outside "
                      f"its slice {want}")
            logits.append(np.asarray(lg, np.float32))
        return want, np.stack(logits)

    with tempfile.TemporaryDirectory() as ckpt:
        vmm = VMM(Mesh(np.array(devs).reshape(2, 2), ("data", "model")),
                  ckpt_root=ckpt)
        try:
            t0 = time.perf_counter()
            alice = vmm.create_vm("alice", (1, 2))
            bob = vmm.create_vm("bob", (1, 2))
            runs = {}
            for t in (alice, bob):
                t.device.open()
                runs[t.name] = decode(t)
                log(f"four-chips: {t.name} decoded {steps} steps on "
                    f"devices {sorted(d.id for d in runs[t.name][0])}")
            check(not runs["alice"][0] & runs["bob"][0], "slices overlap")
            try:
                bob.device.reprogram(alice.program.bitfile)
                check(False, "cross-slice reprogram was allowed")
            except LegalityError as e:
                log(f"four-chips: cross-slice reprogram denied: {e}")
            vmm.destroy_vm("alice")
            vmm.destroy_vm("bob")
            solo = vmm.create_vm("solo", (1, 1))
            solo.device.open()
            solo_devs, solo_logits = decode(solo)
            for name, (_, lg) in runs.items():
                err = close(lg, solo_logits, cfg.vocab)
                agree = float(np.mean(lg.argmax(-1)
                                      == solo_logits.argmax(-1)))
                log(f"four-chips: {name} (1, 2) vs solo (1, 1) on "
                    f"{sorted(d.id for d in solo_devs)}: |Δ|/max = "
                    f"{err:.3e} (tol {LOGIT_TOL:.3e}), argmax agreement "
                    f"{agree:.3f}")
                check(err <= LOGIT_TOL, f"{name} disagrees with solo")
            log(f"four-chips: {time.perf_counter() - t0:.2f} s, compile "
                f"cache hits={vmm.compiler.hits} "
                f"misses={vmm.compiler.misses}")
        finally:
            vmm.shutdown()


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: no {SRC / 'repro'}; run from a checkout")
    sys.path.insert(0, str(SRC))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}")
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"{len(devs)} x {devs[0].device_kind}, jax {jax.__version__}, "
        f"compile cache {cache} ({n_cached} entries)")

    if args.chips == 4:
        phases = [("four-chips", lambda: phase_four_chips(args.seed))]
    else:
        phases = [("serve", phase_serve),
                  ("kernels-vs-xla",
                   lambda: phase_kernels_vs_xla(args.seed)),
                  ("train", phase_train)]
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
        log(f"phase {name} ok in {time.perf_counter() - t0:.2f} s, "
            f"device 0 peak {peak / 2**30:.2f} GiB")
    n_after = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"compile cache {cache}: {n_cached} -> {n_after} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
