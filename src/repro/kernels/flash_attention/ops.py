"""Public wrapper: model layout (B,S,H,hd) ↔ kernel layout (B,H,S,hd),
padding, auto-interpret, and a custom_vjp whose backward recomputes
through the XLA reference (fwd speed where it matters — prefill/serve —
with a correct, if unfused, training path)."""
import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.common import round_up
from repro.kernels.flash_attention.flash_attention import (BK, BQ,
                                                           flash_attention)
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fa(q, k, v, causal, window):
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=common.use_interpret(),
                           bq=min(BQ, q.shape[2]), bk=min(BK, k.shape[2]))


def _fa_fwd(q, k, v, causal, window):
    return _fa(q, k, v, causal, window), (q, k, v)


def _fa_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_ref(q_, k_, v_, causal=causal,
                                               window=window), q, k, v)
    return vjp(g)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_op(q, k, v, *, causal=True, window=0):
    """q: (B,S,Hq,hd); k/v: (B,S,Hkv,hd) — model layout in/out."""
    B, Sq, Hq, hd = q.shape
    Sk = k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(BQ, Sq)
    bk = min(BK, Sk)
    sqp, skp = round_up(Sq, bq), round_up(Sk, bk)
    if sqp != Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sqp - Sq), (0, 0)))
    if skp != Sk:
        # padded keys sit at positions ≥ Sk: causal mask kills them for
        # real queries; for bidirectional, mask via a -inf key trick
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, skp - Sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, skp - Sk), (0, 0)))
        assert causal, "bidirectional padding needs Sk % bk == 0"
    out = _fa(qt, kt, vt, causal, window)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
