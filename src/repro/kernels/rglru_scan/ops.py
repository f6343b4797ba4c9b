"""Public wrapper with padding + auto-interpret."""
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.common import round_up
from repro.kernels.rglru_scan.rglru_scan import BD, BS, rglru_scan


def rglru_scan_op(a, b, h0):
    B, S, D = a.shape
    bs, bd = min(BS, S), min(BD, D)
    sp, dp = round_up(S, bs), round_up(D, bd)
    if (sp, dp) != (S, D):
        # padding with a=1, b=0 leaves the carried state unchanged
        a = jnp.pad(a, ((0, 0), (0, sp - S), (0, dp - D)),
                    constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, sp - S), (0, dp - D)))
        h0 = jnp.pad(h0, ((0, 0), (0, dp - D)))
    out = rglru_scan(a, b, h0, interpret=common.use_interpret(), bs=bs, bd=bd)
    return out[:, :S, :D]
