"""RG-LRU linear-recurrence scan (Griffin / recurrentgemma hot-spot).

h_t = a_t ⊙ h_{t-1} + b_t — a diagonal linear recurrence. TPU adaptation:
the channel dimension is tiled across parallel grid steps (VPU lanes carry
128 channels each); the *sequence* runs as the innermost sequential grid
dimension with the hidden state carried in VMEM scratch across grid steps,
and a fori_loop inside each block. This is a *streaming* scan: HBM traffic
is exactly 2 reads + 1 write per element (roofline-optimal for a
memory-bound recurrence), unlike the O(S log S) associative-scan XLA path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BS, BD = 256, 512


def _kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, bs, ns):
    sidx = pl.program_id(2)

    @pl.when(sidx == 0)
    def _init():
        h_ref[...] = h0_ref[0]                       # (1, bd)

    def body(t, h):
        a_t = a_ref[0, pl.ds(t, 1), :]               # (1, bd)
        b_t = b_ref[0, pl.ds(t, 1), :]
        h_new = a_t * h + b_t
        o_ref[0, pl.ds(t, 1), :] = h_new
        return h_new

    h_ref[...] = jax.lax.fori_loop(0, bs, body, h_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret", "bs", "bd"))
def rglru_scan(a, b, h0, *, interpret=False, bs=BS, bd=BD):
    """a, b: (B, S, D) fp32 decay/input; h0: (B, D) fp32 → h: (B, S, D)."""
    B, S, D = a.shape
    bs = min(bs, S)
    bd = min(bd, D)
    assert S % bs == 0 and D % bd == 0
    ns = S // bs
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, ns=ns),
        grid=(B, D // bd, ns),
        in_specs=[pl.BlockSpec((1, bs, bd), lambda i, j, s: (i, s, j)),
                  pl.BlockSpec((1, bs, bd), lambda i, j, s: (i, s, j)),
                  pl.BlockSpec((1, 1, bd), lambda i, j, s: (i, 0, j))],
        out_specs=pl.BlockSpec((1, bs, bd), lambda i, j, s: (i, s, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0[:, None])
