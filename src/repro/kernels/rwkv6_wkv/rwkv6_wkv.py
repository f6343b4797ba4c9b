"""RWKV-6 WKV recurrence (chunkwise-parallel) — the rwkv6-7b hot-spot.

The same chunked algorithm as models/recurrent.py::_wkv_chunk_scan, with
the chunk loop as the innermost sequential grid dimension and the (K,V)
matrix state carried in VMEM scratch. All pairwise decays are computed in
log space with non-positive exponents (underflow == exact decay-to-zero),
so the kernel is numerically safe at any decay rate — the property that
lets the chunk size be a VMEM-tiling choice rather than a numerics one.

Grid: (B·H, S/C) — batch×head parallel, chunks sequential. Per-chunk work
is three (C×K)·(K×V) MXU dots + C (C,K) VPU elementwise tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 32


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sf_ref,
            s_ref, *, nc, c):
    cidx = pl.program_id(1)

    @pl.when(cidx == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]                    # (K, V)

    r = r_ref[0, 0]                                  # (c, K)
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    lw = lw_ref[0, 0]                                # (c, K) ≤ 0
    u = u_ref[0]                                     # (1, K)

    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum); full f32 precision, the decays are exponentiated below
    L = jax.lax.dot_general((ii >= jj).astype(jnp.float32), lw,
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    Lp = L - lw                                      # exclusive
    s = s_ref[...]

    # inter-chunk: read decayed carried state
    o = jax.lax.dot_general(r * jnp.exp(Lp), s, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (c, V)

    # intra-chunk: pairwise per-channel decays, log-space safe, one key
    # row j at a time (2-D tiles only: Mosaic has no (c, c, K) broadcast)
    later = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    for j in range(c - 1):
        decay = jnp.exp(jnp.minimum(Lp - L[j:j + 1], 0.0))          # (c, K)
        score = (r * k[j:j + 1] * decay).sum(-1, keepdims=True)     # (c, 1)
        o = o + jnp.where(later > j, score, 0.0) * v[j:j + 1]
    bonus = (r * u * k).sum(-1, keepdims=True)                    # (c, 1)
    o = o + bonus * v
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # state update
    L_last = L[-1:, :]                                            # (1, K)
    k_dec = k * jnp.exp(L_last - L)                               # (c, K)
    s_new = jnp.exp(L_last).T * s + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = s_new

    @pl.when(cidx == nc - 1)
    def _flush():
        sf_ref[0, 0] = s_new


@functools.partial(jax.jit, static_argnames=("interpret", "chunk"))
def rwkv6_wkv(r, k, v, logw, u, s0, *, interpret=False, chunk=CHUNK):
    """r/k/v/logw: (B,H,S,K) fp32; u: (H,K); s0: (B,H,K,V=K fp32).

    → (o: (B,H,S,K) fp32, s_final: (B,H,K,K))."""
    B, H, S, K = r.shape
    c = min(chunk, S)
    assert S % c == 0
    nc = S // c
    grid = (B * H, nc)
    io_spec = pl.BlockSpec((1, 1, c, K), lambda g, ci: (g // H, g % H, ci, 0))
    u_spec = pl.BlockSpec((1, 1, K), lambda g, ci: (g % H, 0, 0))
    s_spec = pl.BlockSpec((1, 1, K, K), lambda g, ci: (g // H, g % H, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, nc=nc, c=c),
        grid=grid,
        in_specs=[io_spec, io_spec, io_spec, io_spec, u_spec, s_spec],
        out_specs=(io_spec, s_spec),
        out_shape=(jax.ShapeDtypeStruct((B, H, S, K), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, K, K), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u[:, None], s0)
