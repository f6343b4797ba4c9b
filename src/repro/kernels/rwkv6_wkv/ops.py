"""Public wrapper with sequence padding + auto-interpret."""
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.common import round_up
from repro.kernels.rwkv6_wkv.rwkv6_wkv import CHUNK, rwkv6_wkv


def rwkv6_wkv_op(r, k, v, logw, u, s0, chunk=CHUNK):
    B, H, S, K = r.shape
    c = min(chunk, S)
    sp = round_up(S, c)
    if sp != S:
        pad = ((0, 0), (0, 0), (0, sp - S), (0, 0))
        # k=r=0, logw=0 → padded steps change nothing
        r, k, v, logw = (jnp.pad(t, pad) for t in (r, k, v, logw))
    o, s_fin = rwkv6_wkv(r, k, v, logw, u, s0,
                         interpret=common.use_interpret(), chunk=c)
    return o[:, :, :S], s_fin
