"""jit'd public wrapper with shape padding + auto-interpret."""
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.common import round_up
from repro.kernels.matmul.matmul import BM, BK, BN, matmul


def matmul_op(x, y, bm=BM, bk=BK, bn=BN):
    m, k = x.shape
    _, n = y.shape
    bm_, bk_, bn_ = min(bm, m), min(bk, k), min(bn, n)
    mp, kp, np_ = round_up(m, bm_), round_up(k, bk_), round_up(n, bn_)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    yp = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
    out = matmul(xp, yp, interpret=common.use_interpret(),
                 bm=bm_, bk=bk_, bn=bn_)
    return out[:m, :n]
