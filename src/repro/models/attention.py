"""Attention: GQA/MQA/MHA, causal + bidirectional + sliding-window,
memory-efficient chunked (online-softmax) prefill/train path, ring-buffer
decode path, and cross-attention for enc-dec models.

Memory strategy (XLA path — the Pallas flash kernel is the TPU-native
equivalent in ``repro.kernels.flash_attention``):

* S ≤ _DIRECT_MAX: one dense masked score tensor.
* sliding-window: per-query-chunk *banded* attention — a static-size KV band
  is dynamically sliced per chunk, so FLOPs/bytes stay O(S·(W+Cq)) instead
  of O(S²).
* long full attention: outer scan over query chunks, inner scan over KV
  chunks with an online-softmax carry — O(S) live memory.

GQA is computed in grouped form (B, S, Hkv, G, hd) — no materialized
KV repetition.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import use_pallas
from repro.models.layers import apply_rope, dense_init, dt

_DIRECT_MAX = 2048      # S at or below which the dense path is used
_CHUNK_Q = 512
_CHUNK_K = 512
_NEG = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attn(cfg, key, n_heads=None, n_kv=None, d_model=None):
    d = d_model or cfg.d_model
    hq = n_heads or cfg.n_heads
    hkv = n_kv or cfg.n_kv_heads
    hd = cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd, cfg.param_dtype).reshape(d, hq, hd),
        "wk": dense_init(ks[1], d, hkv * hd, cfg.param_dtype).reshape(d, hkv, hd),
        "wv": dense_init(ks[2], d, hkv * hd, cfg.param_dtype).reshape(d, hkv, hd),
        "wo": dense_init(ks[3], hq * hd, d, cfg.param_dtype).reshape(hq, hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), dt(cfg.param_dtype))
        p["bk"] = jnp.zeros((hkv, hd), dt(cfg.param_dtype))
        p["bv"] = jnp.zeros((hkv, hd), dt(cfg.param_dtype))
    return p


def _project_qkv(cfg, p, x, kv_x=None):
    cd = dt(cfg.compute_dtype)
    x = x.astype(cd)
    kv_x = x if kv_x is None else kv_x.astype(cd)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"].astype(cd))
    if "bq" in p:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return q, k, v


def _out_proj(cfg, p, o):
    cd = dt(cfg.compute_dtype)
    return jnp.einsum("bshk,hkd->bsd", o.astype(cd), p["wo"].astype(cd))


# ---------------------------------------------------------------------------
# Core attention maths (grouped GQA layout)
# ---------------------------------------------------------------------------


def _grouped(q, n_kv):
    B, S, Hq, hd = q.shape
    return q.reshape(B, S, n_kv, Hq // n_kv, hd)


def _mask_bias(q_pos, k_pos, causal, window):
    """(Sq, Sk) additive fp32 bias from absolute positions."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        ok &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(ok, 0.0, _NEG).astype(jnp.float32)


def _direct(q, k, v, bias, scale):
    """q (B,Sq,Hkv,G,hd); k/v (B,Sk,Hkv,hd); bias (Sq,Sk)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale
    s = s + bias[None, None, None]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o


def _chunked_full(q, k, v, q_pos, k_pos, causal, scale):
    """Outer scan over Q chunks, inner online-softmax scan over KV chunks."""
    B, Sq, Hkv, G, hd = q.shape
    Sk = k.shape[1]
    cq = min(_CHUNK_Q, Sq)
    ck = min(_CHUNK_K, Sk)
    nq, nk = Sq // cq, Sk // ck
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, Sk, cq, ck)

    qc = q.reshape(B, nq, cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_pos.reshape(nq, cq)
    kc = k.reshape(B, nk, ck, Hkv, hd)
    vc = v.reshape(B, nk, ck, Hkv, hd)
    kp = k_pos.reshape(nk, ck)

    def q_step(_, qi):
        q_i, qp_i = qi
        acc0 = jnp.zeros((B, cq, Hkv, G, hd), jnp.float32)
        m0 = jnp.full((B, cq, Hkv, G), _NEG, jnp.float32)
        l0 = jnp.zeros((B, cq, Hkv, G), jnp.float32)

        def kv_step(carry, kj):
            acc, m, l = carry
            k_j, v_j, kp_j = kj
            s = jnp.einsum("bqhgd,bkhd->bqhgk", q_i, k_j).astype(
                jnp.float32) * scale
            if causal:
                bad = qp_i[:, None] < kp_j[None, :]
                s = s + jnp.where(bad, _NEG, 0.0)[None, :, None, None, :]
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(v_j.dtype), v_j).astype(
                    jnp.float32)
            return (acc_new, m_new, l_new), None

        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), kp))
        return None, (acc / jnp.maximum(l, 1e-30)[..., None])

    _, o = jax.lax.scan(q_step, None, (qc, qp))
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, G, hd)
    return o.astype(q.dtype)


def _banded_swa(q, k, v, q_pos, k_pos, window, causal, scale):
    """Sliding-window attention with static-size KV bands per Q chunk."""
    B, Sq, Hkv, G, hd = q.shape
    Sk = k.shape[1]
    cq = min(_CHUNK_Q, Sq)
    nq = Sq // cq
    band = int(min(Sk, int(np.ceil(window / cq) + 1) * cq))

    qc = q.reshape(B, nq, cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_pos.reshape(nq, cq)

    def q_step(_, qi):
        q_i, qp_i = qi
        # band start: aligned so that [start, start+band) covers
        # [chunk_end - window + 1, chunk_end]
        start = jnp.clip(qp_i[-1] - (band - 1), 0, Sk - band)
        # absolute kv positions are offset-consistent with k_pos[0]
        start = start - k_pos[0]
        start = jnp.clip(start, 0, Sk - band)
        k_b = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        v_b = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        kp_b = jax.lax.dynamic_slice_in_dim(k_pos, start, band, axis=0)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", q_i, k_b).astype(
            jnp.float32) * scale
        ok = (qp_i[:, None] - kp_b[None, :]) < window
        if causal:
            ok &= qp_i[:, None] >= kp_b[None, :]
        s = s + jnp.where(ok, 0.0, _NEG)[None, :, None, None, :]
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(v_b.dtype), v_b)
        return None, o

    _, o = jax.lax.scan(q_step, None, (qc, qp))
    return o.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, G, hd).astype(
        q.dtype)


def repeat_kv(k, n_rep):
    """GQA KV-head repetition. Done at compute time so the head axis of
    every attention operand shards evenly over the model mesh axis (KV-head
    counts 1/4/8 do not divide a 16-wide axis; repeated heads do)."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def attention_core(q, k, v, *, causal, window, q_pos, k_pos):
    """Dispatch: q (B,Sq,Hq,hd) ungrouped; k/v (B,Sk,Hkv,hd)."""
    Hq = q.shape[2]
    k = repeat_kv(k, Hq // k.shape[2])
    v = repeat_kv(v, Hq // v.shape[2])
    Hkv = k.shape[2]
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    qg = _grouped(q, Hkv)
    Sq, Sk = q.shape[1], k.shape[1]
    # direct whenever the KV side is short (scores mem ∝ Sq·Sk): covers
    # short self-attention AND long-query×short-KV cross-attention
    # (whisper decoder 32k × 1500 encoder frames)
    if Sk <= _DIRECT_MAX:
        bias = _mask_bias(q_pos, k_pos, causal, window)
        o = _direct(qg, k, v, bias, scale)      # (B, Sq, Hkv, G, hd)
    elif window > 0 and window < Sk:
        o = _banded_swa(qg, k, v, q_pos, k_pos, window, causal, scale)
    else:
        o = _chunked_full(qg, k, v, q_pos, k_pos, causal, scale)
    B, _, _, _, _ = qg.shape
    return o.reshape(B, Sq, q.shape[2], hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def attn_full(cfg, p, x, *, causal=True, window=0, positions=None,
              make_cache=False, cache_capacity=0, kv_x=None, mesh=None):
    """Self- or cross-attention over a full sequence.

    Returns (y, cache|None). Cache layout: {"k","v"}: (B, C, Hkv, hd) ring
    (slot = pos % C) in compute dtype. ``mesh`` (a multi-device program's
    mesh) runs the flash kernel per batch × head shard.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, kv_x=kv_x)
    if positions is None:
        positions = jnp.arange(S)
    if cfg.use_rope and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    k_pos = jnp.arange(k.shape[1]) if kv_x is not None else positions
    if (use_pallas() and kv_x is None and causal
            and q.shape[1] == k.shape[1]):
        from repro.kernels.flash_attention.ops import flash_attention_op

        def flash(q, k, v):
            return flash_attention_op(q, k, v, causal=True, window=window)
        if mesh is not None and mesh.size > 1:
            # a Mosaic kernel is never auto-partitioned: run it per shard
            flash = jax.shard_map(flash, mesh=mesh,
                                  in_specs=(_heads_spec(mesh, q, k),) * 3,
                                  out_specs=_heads_spec(mesh, q, k),
                                  check_vma=False)
        y = flash(q, k, v)
    else:
        y = attention_core(q, k, v, causal=causal and kv_x is None,
                           window=window, q_pos=positions, k_pos=k_pos)
    y = _out_proj(cfg, p, y)
    cache = None
    if make_cache:
        C = cache_capacity or S
        if C >= S:
            pad = [(0, 0), (0, C - S), (0, 0), (0, 0)]
            cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
        else:
            # keep last C tokens, ring-ordered: position p lives at p % C
            kl, vl = k[:, S - C:], v[:, S - C:]
            shift = (S - C) % C
            cache = {"k": jnp.roll(kl, shift, axis=1),
                     "v": jnp.roll(vl, shift, axis=1)}
    return y, cache


def cross_kv(cfg, p, enc_out):
    """Precompute cross-attention K/V from encoder output (prefill)."""
    cd = dt(cfg.compute_dtype)
    e = enc_out.astype(cd)
    k = jnp.einsum("bsd,dhk->bshk", e, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", e, p["wv"].astype(cd))
    if "bk" in p:
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Decode (one token against a ring cache)
# ---------------------------------------------------------------------------


def attn_decode(cfg, p, x1, cache, pos, *, window=0, mesh=None):
    """x1 (B,1,D); cache ring {"k","v"} (B,C,Hkv,hd); pos scalar int32.

    The new token's K/V are written at slot pos %% C, then the token attends
    over min(pos+1, C) valid entries. Returns (y (B,1,D), cache').

    When the cache is *sequence-sharded* (kv-heads don't divide the model
    axis, or B=1 long-context), the split-KV shard_map path is used:
    local partial softmax per cache shard + tiny m/l/o reductions —
    measured replacement for a per-layer cache ALL-GATHER that GSPMD
    otherwise inserts (48 GiB/step on internlm2 decode_32k; §Perf).
    """
    B = x1.shape[0]
    C = cache["k"].shape[1]
    Hkv = cache["k"].shape[2]
    hd = cache["k"].shape[3]
    q, k, v = _project_qkv(cfg, p, x1)
    if cfg.use_rope:
        pvec = jnp.full((1,), 0) + pos
        q = apply_rope(q, pvec, cfg.rope_theta)
        k = apply_rope(k, pvec, cfg.rope_theta)

    if mesh is not None and cfg.sharding.decode_splitk:
        seq_axes, b_axes = _cache_seq_axes(mesh, B, Hkv)
        if seq_axes:
            o, ck, cv = _attn_decode_splitk(
                cfg, q, k, v, cache, pos, window, mesh, seq_axes, b_axes)
            return _out_proj(cfg, p, o), {"k": ck, "v": cv}

    slot = jnp.mod(pos, C)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    if use_pallas():
        from repro.kernels.decode_attention.ops import decode_attention_op

        def attend(q, ck, cv, pos):
            return decode_attention_op(q, ck, cv, pos, window=window)
        if mesh is not None and mesh.size > 1:
            # a Mosaic kernel is never auto-partitioned: run it per shard
            # (seq-sharded caches took the split-KV path above)
            from jax.sharding import PartitionSpec as P
            spec = _heads_spec(mesh, q, ck)
            attend = jax.shard_map(attend, mesh=mesh,
                                   in_specs=(spec, spec, spec, P()),
                                   out_specs=spec, check_vma=False)
        o = attend(q, ck, cv, jnp.asarray(pos, jnp.int32))
        return _out_proj(cfg, p, o), {"k": ck, "v": cv}
    scale = 1.0 / np.sqrt(hd)
    Hq = q.shape[2]
    kr = repeat_kv(ck, Hq // Hkv)
    vr = repeat_kv(cv, Hq // Hkv)
    qg = q.reshape(B, 1, Hq, 1, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kr).astype(jnp.float32) * scale
    idx = jnp.arange(C)
    valid = idx <= pos                        # ring not yet full
    valid = valid | (pos >= C)                # ring full → all valid
    if window > 0:
        # slot distance in ring == recency; entry at slot j holds position
        # p_j with p_j ≡ j (mod C); age = (slot - j) mod C
        age = jnp.mod(slot - idx, C)
        valid &= age < window
    s = s + jnp.where(valid, 0.0, _NEG)[None, None, None, None, :]
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pr.astype(vr.dtype), vr)
    o = o.reshape(B, 1, -1, hd)
    y = _out_proj(cfg, p, o)
    return y, {"k": ck, "v": cv}


def attn_decode_paged(cfg, p, x1, pools, positions, block_tables, *,
                      window=0):
    """Paged decode: one token per slot against a shared physical page
    pool (the serving engine's MMU-leased KV memory).

    x1 (B,1,D); pools {"k","v"} (num_pages, page_size, Hkv, hd);
    positions (B,) int32 — write position per slot, -1 for a dead slot
    (its write is dropped and its attention output is zeros);
    block_tables (B, nb) int32 — logical block → physical page, padded
    with any in-range page (masked by length).

    Token layout is linear (token t of slot b lives at page
    ``bt[b, t // ps]`` offset ``t % ps``) — no ring: a slot's pages are
    leased up-front for its prompt and grown on demand, so sliding-window
    masking is a simple ``t >= len - window``. Returns (y, pools').
    """
    B = x1.shape[0]
    P, ps, Hkv, hd = pools["k"].shape
    q, k, v = _project_qkv(cfg, p, x1)
    pos_c = jnp.clip(positions, 0, None)
    if cfg.use_rope:
        q = apply_rope(q, pos_c[:, None], cfg.rope_theta)
        k = apply_rope(k, pos_c[:, None], cfg.rope_theta)

    nb = block_tables.shape[1]
    blk = jnp.clip(pos_c // ps, 0, nb - 1)
    page = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    # dead slots scatter to the out-of-range sentinel page → dropped
    page = jnp.where(positions >= 0, page, P)
    off = pos_c % ps
    ck = pools["k"].at[page, off].set(k[:, 0], mode="drop")
    cv = pools["v"].at[page, off].set(v[:, 0], mode="drop")
    lengths = jnp.maximum(positions + 1, 0)          # dead slot → 0

    if use_pallas():
        # fused step: the new token's K/V ride in VMEM and are
        # substituted in-register at index lengths-1, so the sweep reads
        # the *pre-scatter* pools and never waits on the persist-scatter
        # above (which still runs, for the next step)
        from repro.kernels.decode_attention.ops import fused_decode_step_op
        o = fused_decode_step_op(q, k, v, pools["k"], pools["v"], lengths,
                                 block_tables, window=window)
        return _out_proj(cfg, p, o), {"k": ck, "v": cv}

    # XLA fallback: gather the slot's pages, grouped-GQA single-token
    # attention with a linear validity mask (interpret-free CI path).
    S = nb * ps
    kb = ck[block_tables].reshape(B, S, Hkv, hd)
    vb = cv[block_tables].reshape(B, S, Hkv, hd)
    Hq = q.shape[2]
    G = Hq // Hkv
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   kb.astype(jnp.float32)) * scale
    tok = jnp.arange(S)
    valid = tok[None] < lengths[:, None]
    if window > 0:
        valid &= tok[None] >= (lengths[:, None] - window)
    s = jnp.where(valid[:, None, None], s, _NEG)
    pr = jax.nn.softmax(s, axis=-1)
    pr = jnp.where(valid[:, None, None], pr, 0.0)     # dead slots → zeros
    o = jnp.einsum("bhgk,bkhd->bhgd", pr.astype(vb.dtype), vb)
    y = _out_proj(cfg, p, o.reshape(B, 1, Hq, hd))
    return y, {"k": ck, "v": cv}


def attn_prefill_chunk_paged(cfg, p, x, pools, positions, block_row, *,
                             window=0):
    """One slot's prompt *chunk* against its leased pages (chunked
    prefill: the engine interleaves these bounded writes with decode
    steps so a newcomer never stalls the batch).

    x (1, L, D) chunk of the prompt; positions (L,) absolute token
    indices [start, start+L); block_row (nb,) the slot's logical block →
    physical page map; pools as in :func:`attn_decode_paged`.

    The chunk's K/V are scattered into the pool, then the chunk attends
    causally over tokens [0, start+L): earlier chunks' tokens are
    gathered from the pool, and any stale data at k_pos > start+L-1
    (pages leased but not yet written, or recycled from a freed slot)
    is provably masked by causality. Returns (y (1, L, D), pools').
    """
    _, ps, Hkv, hd = pools["k"].shape
    q, k, v = _project_qkv(cfg, p, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    pages = block_row[positions // ps]
    offs = positions % ps
    ck = pools["k"].at[pages, offs].set(k[0])
    cv = pools["v"].at[pages, offs].set(v[0])
    nb = block_row.shape[0]
    S = nb * ps
    kb = ck[block_row].reshape(1, S, Hkv, hd)
    vb = cv[block_row].reshape(1, S, Hkv, hd)
    y = attention_core(q, kb, vb, causal=True, window=window,
                       q_pos=positions, k_pos=jnp.arange(S))
    return _out_proj(cfg, p, y), {"k": ck, "v": cv}


def _heads_spec(mesh, q, k):
    """(B, S, H, hd) spec sharding batch over the data axes and heads over
    ``model``, each only where it divides (q and k heads alike)."""
    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    tp = int(mesh.shape["model"]) if "model" in names else 1
    b = dp if dp and q.shape[0] % dp_size == 0 else None
    h = ("model" if tp > 1 and q.shape[2] % tp == 0
         and k.shape[2] % tp == 0 else None)
    return P(b, None, h, None)


def _cache_seq_axes(mesh, B, Hkv):
    """Mirror of partition.cache_pspecs: which axes shard the cache seq
    dim, and which shard the batch dim."""
    import numpy as np
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    tp = int(mesh.shape["model"]) if "model" in names else 1
    b_ok = dp and B % dp_size == 0
    if Hkv % tp == 0:
        return (), (dp if b_ok else None)      # heads shard: no split-KV
    if b_ok:
        return ("model",), dp
    return ("data", "model") if "data" in names else ("model",), None


def _attn_decode_splitk(cfg, q, k_new, v_new, cache, pos, window, mesh,
                        seq_axes, b_axes):
    """Split-KV decode: each shard owns a contiguous cache seq block."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    B, _, Hq, hd = q.shape
    C = cache["k"].shape[1]
    Hkv = cache["k"].shape[2]
    n_seq = int(np.prod([mesh.shape[a] for a in seq_axes]))
    C_loc = C // n_seq
    G = Hq // Hkv
    scale = 1.0 / np.sqrt(hd)

    def inner(q, kn, vn, ck, cv, pos):
        # ck/cv (B_loc, C_loc, Hkv, hd); q/kn/vn replicated over seq axes
        sidx = jax.lax.axis_index(seq_axes[0])
        for a in seq_axes[1:]:
            sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
        base = sidx * C_loc
        slot = jnp.mod(pos, C)
        lslot = jnp.clip(slot - base, 0, C_loc - 1)
        own = (slot >= base) & (slot < base + C_loc)
        ck_w = jax.lax.dynamic_update_slice(ck, kn, (0, lslot, 0, 0))
        cv_w = jax.lax.dynamic_update_slice(cv, vn, (0, lslot, 0, 0))
        ck = jnp.where(own, ck_w, ck)
        cv = jnp.where(own, cv_w, cv)

        # grouped GQA math — no materialized KV repetition (the repeat
        # showed up as the dominant decode HBM stream; §Perf iteration)
        qg = q.reshape(q.shape[0], Hkv, G, hd)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, ck).astype(
            jnp.float32) * scale                              # (B,Hkv,G,C)
        gidx = base + jnp.arange(C_loc)
        valid = (gidx <= pos) | (pos >= C)
        if window > 0:
            age = jnp.mod(slot - gidx, C)
            valid &= age < window
        s = jnp.where(valid[None, None, None, :], s, _NEG)
        m_loc = s.max(axis=-1)                               # (B, Hkv, G)
        m = m_loc
        for a in seq_axes:
            m = jax.lax.pmax(m, a)
        pr = jnp.exp(s - m[..., None])
        pr = jnp.where(valid[None, None, None, :], pr, 0.0)
        l_loc = pr.sum(axis=-1)
        o_loc = jnp.einsum("bhgk,bkhd->bhgd", pr.astype(cv.dtype),
                           cv).astype(jnp.float32)
        l, o = l_loc, o_loc
        for a in seq_axes:
            l = jax.lax.psum(l, a)
            o = jax.lax.psum(o, a)
        o = o / jnp.maximum(l, 1e-30)[..., None]
        o = o.reshape(o.shape[0], Hq, hd)
        return o[:, None].astype(q.dtype), ck, cv

    qspec = P(b_axes, None, None, None)
    seq_sh = seq_axes[0] if len(seq_axes) == 1 else tuple(seq_axes)
    cspec = P(b_axes, seq_sh, None, None)
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(qspec, qspec, qspec, cspec, cspec, P()),
        out_specs=(qspec, cspec, cspec),
        check_vma=False)
    o, ck, cv = fn(q, k_new, v_new, cache["k"], cache["v"],
                   jnp.asarray(pos, jnp.int32))
    return o, ck, cv


def cross_attn_decode(cfg, p, x1, ckv):
    """Decode-time cross attention against precomputed encoder K/V."""
    B = x1.shape[0]
    cd = dt(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x1.astype(cd), p["wq"].astype(cd))
    if "bq" in p:
        q = q + p["bq"].astype(cd)
    hd = q.shape[-1]
    Hkv = ckv["k"].shape[2]
    qg = q.reshape(B, 1, Hkv, -1, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ckv["k"]).astype(
        jnp.float32) / np.sqrt(hd)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pr.astype(ckv["v"].dtype), ckv["v"])
    return _out_proj(cfg, p, o.reshape(B, 1, -1, hd))
