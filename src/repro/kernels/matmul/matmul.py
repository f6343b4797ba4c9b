"""Blocked matmul — the paper's benchmark app #1, as an MXU-native
Pallas kernel.

Hardware codesign (DESIGN.md §2): tiles are multiples of the 128×128 MXU
systolic array; the K reduction runs as the innermost sequential grid
dimension with an fp32 VMEM accumulator (output written once on the last
K step), so each (i,j) output tile stays resident in VMEM across the
reduction — the TPU analogue of the paper's DSP-array matrix engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM, BK, BN = 256, 512, 256


def _kernel(x_ref, y_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "bm", "bk", "bn"))
def matmul(x, y, *, interpret=False, bm=BM, bk=BK, bn=BN):
    m, k = x.shape
    k2, n = y.shape
    assert k == k2
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n)
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, y)
