"""Public wrapper (model cache layout (B,C,H,hd) ↔ kernel (B,H,C,hd)).

Dispatches on cache type: a contiguous per-slot cache (B,C,Hkv,hd) with a
shared scalar ``pos`` takes the reference ring-cache kernel; passing
``block_tables`` selects the paged kernel, where the cache is a shared
physical page pool (num_pages, page_size, Hkv, hd) and ``pos`` is the
per-slot ``lengths`` vector (B,).
"""
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.decode_attention.decode_attention import (
    BKV, decode_attention, fused_paged_decode_attention,
    paged_decode_attention, sample_tokens)


def decode_attention_op(q, k_cache, v_cache, pos, *, window=0,
                        block_tables=None):
    """q: (B,1,Hq,hd).

    Contiguous: caches (B,C,Hkv,hd); pos () int32 shared position.
    Paged (``block_tables`` given): caches (P,ps,Hkv,hd) page pools;
    pos (B,) int32 per-slot valid lengths; block_tables (B,nb) int32.
    """
    qt = q.transpose(0, 2, 1, 3)
    if block_tables is not None:
        out = paged_decode_attention(
            qt, k_cache, v_cache, jnp.asarray(pos, jnp.int32),
            block_tables, window=window, interpret=common.use_interpret())
        return out.transpose(0, 2, 1, 3)
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    C = kt.shape[2]
    bkv = BKV
    while C % bkv:
        bkv //= 2
    out = decode_attention(qt, kt, vt, jnp.asarray(pos, jnp.int32),
                           window=window, interpret=common.use_interpret(),
                           bkv=max(bkv, 1))
    return out.transpose(0, 2, 1, 3)


def fused_decode_step_op(q, k_new, v_new, k_pages, v_pages, lengths,
                         block_tables, *, window=0):
    """Fused serving step (Pallas): the new token's K/V rides in VMEM
    instead of being read back from the pool it was just scattered to.

    q: (B,1,Hq,hd); k_new/v_new: (B,1,Hkv,hd) this step's projected and
    roped K/V (logical index ``lengths-1``); pages: (P,ps,Hkv,hd) pool
    *without* the new token; lengths (B,) include the new token.
    """
    qt = q.transpose(0, 2, 1, 3)
    out = fused_paged_decode_attention(
        qt, k_new.transpose(0, 2, 1, 3), v_new.transpose(0, 2, 1, 3),
        k_pages, v_pages, jnp.asarray(lengths, jnp.int32), block_tables,
        window=window, interpret=common.use_interpret())
    return out.transpose(0, 2, 1, 3)


def fused_paged_attention_xla(q, k_new, v_new, k_pages, v_pages, lengths,
                              block_tables, *, window=0):
    """Pure-jnp fallback with the same contract as the fused kernel
    (kernel layout: q (B,Hq,1,hd), k_new/v_new (B,Hkv,1,hd))."""
    B, Hq, _, hd = q.shape
    _, ps, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    nb = block_tables.shape[1]
    S = nb * ps
    k = k_pages[block_tables].reshape(B, S, Hkv, hd)
    v = v_pages[block_tables].reshape(B, S, Hkv, hd)
    tok = jnp.arange(S)
    is_new = (tok[None] == lengths[:, None] - 1)[..., None, None]
    k = jnp.where(is_new, k_new.transpose(0, 2, 1, 3), k)
    v = jnp.where(is_new, v_new.transpose(0, 2, 1, 3), v)
    kr = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).astype(jnp.float32)
    vr = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr) * (hd ** -0.5)
    valid = tok[None] < lengths[:, None]
    if window > 0:
        valid = valid & (tok[None] >= lengths[:, None] - window)
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = jnp.where(valid[:, None, None], p, 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vr).astype(q.dtype)


def sample_tokens_op(logits, temps, noise):
    """On-device argmax/Gumbel-max sampling: (B,V)+(B,)+(B,V) → (B,)."""
    return sample_tokens(logits, temps, noise,
                         interpret=common.use_interpret())


def sample_tokens_xla(logits, temps, noise):
    """Pure-jnp fallback for ``sample_tokens`` (same tie semantics:
    jnp.argmax takes the first maximal index)."""
    scores = logits.astype(jnp.float32) + \
        noise.astype(jnp.float32) * temps.astype(jnp.float32)[:, None]
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)
