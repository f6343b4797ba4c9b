"""Flash attention (fwd) — causal/bidirectional GQA with sliding-window
support, as a Pallas TPU kernel.

Hardware codesign (DESIGN.md §2/§6):
* online-softmax streaming over KV blocks — the (Sq, Sk) score matrix never
  leaves VMEM (IO-aware, FlashAttention [arXiv:2205.14135] restructured for
  the TPU memory hierarchy);
* GQA without materialized KV repetition: the kv-head block index is
  *computed in the BlockSpec index_map* (q-head → kv-head arithmetic), so
  each grid step DMAs only its group's KV block;
* fp32 accumulator + m/l state live in VMEM scratch across the sequential
  innermost KV grid dimension; MXU-shaped (bq×hd)·(hd×bk) dots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ, BK = 512, 512
_NEG = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale, causal, window, bq, bk, nk):
    kidx = pl.program_id(2)
    qidx = pl.program_id(1)

    @pl.when(kidx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                  # (bq, hd)
    k = k_ref[0, 0]                                  # (bk, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    qpos = qidx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kidx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= qpos >= kpos
    if window > 0:
        ok &= (qpos - kpos) < window
    s = jnp.where(ok, s, _NEG)

    m_prev = m_ref[:, :1]                            # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
    p = jnp.exp(s - m_new)                           # (bq, bk)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(kidx == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
                           o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "interpret", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, window=0, interpret=False,
                    bq=BQ, bk=BK):
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd); Hkv | Hq. → (B,Hq,Sq,hd)."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    scale = hd ** -0.5
    grid = (B * Hq, nq, nk)

    q_spec = pl.BlockSpec((1, 1, bq, hd),
                          lambda g, i, j: (g // Hq, g % Hq, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda g, i, j: (g // Hq, (g % Hq) // G, j, 0))
    o_spec = pl.BlockSpec((1, 1, bq, hd),
                          lambda g, i, j: (g // Hq, g % Hq, i, 0))

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
