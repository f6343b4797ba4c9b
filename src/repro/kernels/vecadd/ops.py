"""jit'd public wrapper: auto-interpret off-TPU, pads to block multiple."""
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.common import round_up
from repro.kernels.vecadd.vecadd import BLOCK, vecadd


def vecadd_op(x, y, block=BLOCK):
    n = x.shape[0]
    np_ = round_up(n, block)
    if np_ != n:
        x = jnp.pad(x, (0, np_ - n))
        y = jnp.pad(y, (0, np_ - n))
    out = vecadd(x, y, interpret=common.use_interpret(), block=block)
    return out[:n]
