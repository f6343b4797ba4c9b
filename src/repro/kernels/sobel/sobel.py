"""Sobel filter — the paper's benchmark app #2, as a 2-D stencil Pallas
kernel.

TPU adaptation: instead of a line-buffered FPGA pipeline, each grid step
loads an (bh+2, bw+2) *haloed* VMEM tile (overlapping BlockSpec windows via
element-indexed index_map) and computes the 3×3 convolution as shifted
adds on the VPU. Edges use zero padding (handled by the wrapper).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BH, BW = 256, 256

# Gx/Gy Sobel taps
_GX = ((-1, 0, 1), (-2, 0, 2), (-1, 0, 1))
_GY = ((-1, -2, -1), (0, 0, 0), (1, 2, 1))


def _kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)     # (bh+2, bw+2)
    bh = o_ref.shape[0]
    bw = o_ref.shape[1]
    gx = jnp.zeros((bh, bw), jnp.float32)
    gy = jnp.zeros((bh, bw), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            win = x[dy:dy + bh, dx:dx + bw]
            if _GX[dy][dx]:
                gx += _GX[dy][dx] * win
            if _GY[dy][dx]:
                gy += _GY[dy][dx] * win
    o_ref[...] = jnp.sqrt(gx * gx + gy * gy).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "bh", "bw"))
def sobel(x_padded, *, interpret=False, bh=BH, bw=BW):
    """x_padded: (H+2, W+2) zero-padded input → (H, W) gradient magnitude."""
    hp, wp = x_padded.shape
    h, w = hp - 2, wp - 2
    assert h % bh == 0 and w % bw == 0, (h, w, bh, bw)
    return pl.pallas_call(
        _kernel,
        grid=(h // bh, w // bw),
        in_specs=[pl.BlockSpec(
            (pl.Element(bh + 2), pl.Element(bw + 2)),   # overlapping halo
            lambda i, j: (i * bh, j * bw))],            # element offsets
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, w), x_padded.dtype),
        interpret=interpret,
    )(x_padded)
