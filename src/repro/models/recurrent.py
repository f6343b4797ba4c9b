"""Recurrent mixers: Griffin RG-LRU (recurrentgemma) and RWKV-6 "Finch".

TPU adaptation notes (DESIGN.md §2): both recurrences are reformulated from
the papers' GPU kernels into forms XLA schedules well on TPU —

* RG-LRU: a diagonal linear recurrence → ``jax.lax.associative_scan``
  (parallel prefix, O(S log S) work, no serial dependency chain).
* RWKV-6 WKV: matrix-state linear recurrence with per-channel data-dependent
  decay → *chunkwise-parallel* form: intra-chunk pairwise decays are
  materialized per chunk in log-space (all exponents ≤ 0 → numerically safe,
  underflow is exact decay-to-zero), inter-chunk state is carried by a
  ``lax.scan``. The Pallas kernel ``repro.kernels.rwkv6_wkv`` implements the
  same chunked algorithm with VMEM-resident chunks.

States are fp32; parameters in cfg.param_dtype; projections in compute dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import use_pallas
from repro.models.layers import dense_init, dt

RG_CONV_WIDTH = 4
RG_C = 8.0                      # Griffin's fixed gate exponent scale
WKV_CHUNK = 16                  # chunk length for the chunked WKV scan
LORA_MIX = 32                   # RWKV6 ddlerp LoRA rank
LORA_DECAY = 64                 # RWKV6 decay LoRA rank


# ===========================================================================
# RG-LRU (Griffin recurrent block)
# ===========================================================================


def init_rglru(cfg, key):
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    ks = jax.random.split(key, 7)
    pd = cfg.param_dtype
    return {
        "w_x": dense_init(ks[0], d, d, pd),          # recurrent branch in-proj
        "w_g": dense_init(ks[1], d, d, pd),          # gelu gate branch
        "w_o": dense_init(ks[2], d, d, pd),
        "conv_w": (jax.random.normal(ks[3], (RG_CONV_WIDTH, d)) * 0.1).astype(pd),
        "conv_b": jnp.zeros((d,), dt(pd)),
        # block-diagonal (per-head) gate projections — Griffin layout
        "w_ra": dense_init(ks[4], d, dh, pd).reshape(H, dh, dh),
        "w_ix": dense_init(ks[5], d, dh, pd).reshape(H, dh, dh),
        "lam": jax.random.uniform(ks[6], (d,), jnp.float32, 2.0, 6.0),
    }


def _rg_gates(p, xr):
    """xr (B,S,d) → recurrence gate a_log (fp32 ≤0) and input gate i."""
    B, S, d = xr.shape
    H, dh, _ = p["w_ra"].shape
    xh = xr.reshape(B, S, H, dh)
    r = jax.nn.sigmoid(jnp.einsum(
        "bshd,hde->bshe", xh.astype(jnp.float32),
        p["w_ra"].astype(jnp.float32)).reshape(B, S, d))
    i = jax.nn.sigmoid(jnp.einsum(
        "bshd,hde->bshe", xh.astype(jnp.float32),
        p["w_ix"].astype(jnp.float32)).reshape(B, S, d))
    # log a_t = -c · softplus(Λ) · r_t  (≤ 0 ⇒ a_t ∈ (0,1])
    log_a = -RG_C * jax.nn.softplus(p["lam"])[None, None] * r
    return log_a, i


def _rg_conv_full(p, x):
    """Causal depthwise conv width 4 via shifted adds. x (B,S,d)."""
    w, b = p["conv_w"].astype(jnp.float32), p["conv_b"].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    y = xf * w[0]
    for j in range(1, RG_CONV_WIDTH):
        shifted = jnp.pad(xf, ((0, 0), (j, 0), (0, 0)))[:, :-j if j else None]
        y = y + shifted * w[j]
    return (y + b).astype(x.dtype)


def rglru_full(cfg, p, x, h0=None, conv0=None, make_cache=False):
    """Full-sequence Griffin block. x (B,S,d) → (y, cache|None).

    cache = {"h": (B,d) fp32, "conv": (B, 3, d)}.
    """
    cd = dt(cfg.compute_dtype)
    B, S, d = x.shape
    xb = jnp.dot(x.astype(cd), p["w_x"].astype(cd))
    gb = jax.nn.gelu(jnp.dot(x.astype(cd), p["w_g"].astype(cd)))
    if conv0 is not None:
        xb_ext = jnp.concatenate([conv0.astype(cd), xb], axis=1)
        xc = _rg_conv_full(p, xb_ext)[:, RG_CONV_WIDTH - 1:]
    else:
        xc = _rg_conv_full(p, xb)
    log_a, gate_i = _rg_gates(p, xc)
    a = jnp.exp(log_a)                                        # (B,S,d) fp32
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b_in = beta * (gate_i * xc.astype(jnp.float32))

    if use_pallas():
        from repro.kernels.rglru_scan.ops import rglru_scan_op
        h = rglru_scan_op(a, b_in,
                          h0.astype(jnp.float32) if h0 is not None
                          else jnp.zeros((B, d), jnp.float32))
    else:
        if h0 is not None:
            # fold the incoming state in as a virtual step at t=-1
            a = jnp.concatenate([jnp.zeros((B, 1, d), jnp.float32), a],
                                axis=1)
            b_in = jnp.concatenate([h0[:, None].astype(jnp.float32), b_in],
                                   1)

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        _, h = jax.lax.associative_scan(combine, (a, b_in), axis=1)
        if h0 is not None:
            h = h[:, 1:]
    y = jnp.dot((gb.astype(jnp.float32) * h).astype(cd), p["w_o"].astype(cd))
    cache = None
    if make_cache:
        if conv0 is not None:
            # xb_ext = [conv history | chunk] — its tail is correct even
            # when the chunk is shorter than the conv window (chunked
            # prefill's last chunk can be a single token)
            conv = xb_ext[:, -(RG_CONV_WIDTH - 1):].astype(cd)
        elif S >= RG_CONV_WIDTH - 1:
            conv = xb[:, S - (RG_CONV_WIDTH - 1):].astype(cd)
        else:
            conv = jnp.pad(xb, ((0, 0), (RG_CONV_WIDTH - 1 - S, 0), (0, 0)))
        cache = {"h": h[:, -1], "conv": conv}
    return y, cache


def rglru_decode(cfg, p, x1, cache):
    """One-token Griffin step. x1 (B,1,d); cache {"h","conv"}."""
    cd = dt(cfg.compute_dtype)
    B, _, d = x1.shape
    xb = jnp.dot(x1.astype(cd), p["w_x"].astype(cd))          # (B,1,d)
    gb = jax.nn.gelu(jnp.dot(x1.astype(cd), p["w_g"].astype(cd)))
    w, bconv = p["conv_w"].astype(jnp.float32), p["conv_b"].astype(jnp.float32)
    hist = cache["conv"].astype(jnp.float32)                  # (B,3,d) oldest-first
    xc = (xb[:, 0].astype(jnp.float32) * w[0]
          + hist[:, 2] * w[1] + hist[:, 1] * w[2] + hist[:, 0] * w[3]
          + bconv)[:, None]
    log_a, gate_i = _rg_gates(p, xc.astype(cd))
    a = jnp.exp(log_a[:, 0])
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a[:, 0]), 1e-12))
    h = a * cache["h"] + beta * (gate_i[:, 0] * xc[:, 0].astype(jnp.float32))
    y = jnp.dot((gb[:, 0].astype(jnp.float32) * h).astype(cd),
                p["w_o"].astype(cd))[:, None]
    new_conv = jnp.concatenate([hist[:, 1:], xb.astype(jnp.float32)], axis=1)
    return y, {"h": h, "conv": new_conv.astype(cd)}


# ===========================================================================
# RWKV-6 time-mix (WKV) + channel-mix
# ===========================================================================


def init_rwkv_tmix(cfg, key):
    d = cfg.d_model
    dk = cfg.rwkv_head_dim
    H = d // dk
    ks = jax.random.split(key, 12)
    pd = cfg.param_dtype
    return {
        "mu_base": jnp.full((d,), 0.5, dt(pd)),
        "mu_rkvwg": (jax.random.normal(ks[0], (5, d)) * 0.02 + 0.5).astype(pd),
        "mix_A": dense_init(ks[1], d, 5 * LORA_MIX, pd),
        "mix_B": (jax.random.normal(ks[2], (5, LORA_MIX, d)) * 0.02).astype(pd),
        "w_r": dense_init(ks[3], d, d, pd),
        "w_k": dense_init(ks[4], d, d, pd),
        "w_v": dense_init(ks[5], d, d, pd),
        "w_g": dense_init(ks[6], d, d, pd),
        "w_o": dense_init(ks[7], d, d, pd),
        "decay_base": jax.random.uniform(ks[8], (d,), jnp.float32, -7.0, 1.0),
        "decay_A": dense_init(ks[9], d, LORA_DECAY, pd),
        "decay_B": dense_init(ks[10], LORA_DECAY, d, pd),
        "bonus_u": (jax.random.normal(ks[11], (H, dk)) * 0.02).astype(
            jnp.float32),
        "ln_scale": jnp.ones((d,), dt(pd)),
        "ln_bias": jnp.zeros((d,), dt(pd)),
    }


def _ddlerp(p, x, x_prev):
    """RWKV6 data-dependent token-shift lerp → (xr, xk, xv, xw, xg)."""
    cd = x.dtype
    dx = x_prev - x                                            # (B,S,d)
    base = x + dx * p["mu_base"].astype(cd)
    lora = jnp.tanh(jnp.dot(base, p["mix_A"].astype(cd)))      # (B,S,5R)
    B, S, _ = x.shape
    lora = lora.reshape(B, S, 5, LORA_MIX)
    mixes = (p["mu_rkvwg"].astype(cd)[None, None]
             + jnp.einsum("bsfr,frd->bsfd", lora, p["mix_B"].astype(cd)))
    outs = x[:, :, None] + dx[:, :, None] * mixes              # (B,S,5,d)
    return tuple(outs[:, :, i] for i in range(5))


def _wkv_chunk_scan(r, k, v, logw, u, s0):
    """Chunkwise-parallel WKV. r,k,v (B,S,H,K); logw fp32 ≤0; s0 (B,H,K,V).

    Returns (o (B,S,H,V) fp32, s_final).
    """
    B, S, H, K = r.shape
    c = min(WKV_CHUNK, S)
    S_orig = S
    if S % c:
        # pad with k=r=0, logw=0 (w=1): contributes nothing to state/output
        pad = c - S % c
        r, k, v, logw = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                         for t in (r, k, v, logw))
        S = S + pad
    n = S // c

    def to_chunks(t):
        return t.reshape(B, n, c, H, K).transpose(1, 0, 2, 3, 4)

    rc, kc, vc, lwc = map(to_chunks, (r, k, v, logw))

    def step(s, inp):
        r_i, k_i, v_i, lw_i = inp                              # (B,c,H,K)
        L = jnp.cumsum(lw_i, axis=1)                           # inclusive
        Lp = L - lw_i                                          # exclusive
        # inter-chunk: read decayed initial state
        r_dec = r_i * jnp.exp(Lp)
        o = jnp.einsum("bchk,bhkv->bchv", r_dec, s)
        # intra-chunk: pairwise decay in log space (exponents ≤ 0)
        diff = Lp[:, :, None] - L[:, None, :]                  # (B,c,c,H,K)
        ii = jnp.arange(c)
        causal = (ii[:, None] > ii[None, :])[None, :, :, None, None]
        D = jnp.where(causal, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        scores = jnp.einsum("bihk,bjhk,bijhk->bijh", r_i, k_i, D)
        o = o + jnp.einsum("bijh,bjhv->bihv", scores, v_i)
        # bonus (current token)
        sb = jnp.einsum("bihk,hk,bihk->bih", r_i, u, k_i)
        o = o + sb[..., None] * v_i
        # state update
        L_last = L[:, -1]                                      # (B,H,K)
        k_dec = k_i * jnp.exp(L_last[:, None] - L)
        s_new = jnp.exp(L_last)[..., None] * s + jnp.einsum(
            "bjhk,bjhv->bhkv", k_dec, v_i)
        return s_new, o

    s_fin, oc = jax.lax.scan(step, s0, (rc, kc, vc, lwc))
    o = oc.transpose(1, 0, 2, 3, 4).reshape(B, S, H, -1)
    return o[:, :S_orig], s_fin


def _head_groupnorm(p, o_flat, H):
    """Per-head LayerNorm (RWKV's GroupNorm with H groups)."""
    B, S, d = o_flat.shape
    oh = o_flat.reshape(B, S, H, d // H)
    mu = oh.mean(-1, keepdims=True)
    var = oh.var(-1, keepdims=True)
    oh = (oh - mu) * jax.lax.rsqrt(var + 1e-5)
    out = oh.reshape(B, S, d)
    return out * p["ln_scale"].astype(out.dtype) + p["ln_bias"].astype(
        out.dtype)


def rwkv_tmix_full(cfg, p, x, cache=None, make_cache=False):
    """Full-sequence RWKV6 time-mix. cache {"shift": (B,d), "s": (B,H,K,V)}."""
    cd = dt(cfg.compute_dtype)
    B, S, d = x.shape
    dk = cfg.rwkv_head_dim
    H = d // dk
    x = x.astype(cd)
    prev0 = (cache["shift"].astype(cd)[:, None] if cache is not None
             else jnp.zeros((B, 1, d), cd))
    x_prev = jnp.concatenate([prev0, x[:, :-1]], axis=1)
    xr, xk, xv, xw, xg = _ddlerp(p, x, x_prev)
    r = jnp.dot(xr, p["w_r"].astype(cd)).reshape(B, S, H, dk).astype(
        jnp.float32)
    k = jnp.dot(xk, p["w_k"].astype(cd)).reshape(B, S, H, dk).astype(
        jnp.float32)
    v = jnp.dot(xv, p["w_v"].astype(cd)).reshape(B, S, H, dk).astype(
        jnp.float32)
    g = jnp.dot(xg, p["w_g"].astype(cd))
    ww = (p["decay_base"][None, None]
          + jnp.dot(jnp.tanh(jnp.dot(xw, p["decay_A"].astype(cd))),
                    p["decay_B"].astype(cd)).astype(jnp.float32))
    logw = -jnp.exp(ww).reshape(B, S, H, dk)                   # ≤ 0
    s0 = (cache["s"] if cache is not None
          else jnp.zeros((B, H, dk, dk), jnp.float32))
    if use_pallas():
        from repro.kernels.rwkv6_wkv.ops import rwkv6_wkv_op
        ot, s_fin = rwkv6_wkv_op(
            r.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), logw.transpose(0, 2, 1, 3),
            p["bonus_u"].astype(jnp.float32), s0)
        o = ot.transpose(0, 2, 1, 3)
    else:
        o, s_fin = _wkv_chunk_scan(r, k, v, logw, p["bonus_u"], s0)
    o = _head_groupnorm(p, o.reshape(B, S, d).astype(cd), H)
    y = jnp.dot(o * jax.nn.silu(g), p["w_o"].astype(cd))
    new_cache = None
    if make_cache:
        new_cache = {"shift": x[:, -1], "s": s_fin}
    return y, new_cache


def rwkv_tmix_decode(cfg, p, x1, cache):
    """One-token RWKV6 step."""
    cd = dt(cfg.compute_dtype)
    B, _, d = x1.shape
    dk = cfg.rwkv_head_dim
    H = d // dk
    x1 = x1.astype(cd)
    x_prev = cache["shift"].astype(cd)[:, None]
    xr, xk, xv, xw, xg = _ddlerp(p, x1, x_prev)
    r = jnp.dot(xr, p["w_r"].astype(cd)).reshape(B, H, dk).astype(jnp.float32)
    k = jnp.dot(xk, p["w_k"].astype(cd)).reshape(B, H, dk).astype(jnp.float32)
    v = jnp.dot(xv, p["w_v"].astype(cd)).reshape(B, H, dk).astype(jnp.float32)
    g = jnp.dot(xg, p["w_g"].astype(cd))[:, 0]
    ww = (p["decay_base"][None, None]
          + jnp.dot(jnp.tanh(jnp.dot(xw, p["decay_A"].astype(cd))),
                    p["decay_B"].astype(cd)).astype(jnp.float32))
    w = jnp.exp(-jnp.exp(ww)).reshape(B, H, dk)
    s = cache["s"]                                             # (B,H,K,V)
    o = (jnp.einsum("bhk,bhkv->bhv", r, s)
         + jnp.einsum("bhk,hk,bhk->bh", r, p["bonus_u"], k)[..., None] * v)
    s_new = w[..., None] * s + jnp.einsum("bhk,bhv->bhkv", k, v)
    o = _head_groupnorm(p, o.reshape(B, 1, d).astype(cd), H)[:, 0]
    y = jnp.dot(o * jax.nn.silu(g), p["w_o"].astype(cd))[:, None]
    return y, {"shift": x1[:, 0], "s": s_new}


# ---------------------------------------------------------------------------
# RWKV channel-mix (the rwkv "FFN"; has a token-shift state)
# ---------------------------------------------------------------------------


def init_channelmix(cfg, key):
    d, dff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    pd = cfg.param_dtype
    return {
        "mu_k": jnp.full((d,), 0.5, dt(pd)),
        "mu_r": jnp.full((d,), 0.5, dt(pd)),
        "w_k": dense_init(ks[0], d, dff, pd),
        "w_v": dense_init(ks[1], dff, d, pd),
        "w_r": dense_init(ks[2], d, d, pd),
    }


def channelmix_full(cfg, p, x, cache=None, make_cache=False):
    cd = dt(cfg.compute_dtype)
    B, S, d = x.shape
    x = x.astype(cd)
    prev0 = (cache["shift"].astype(cd)[:, None] if cache is not None
             else jnp.zeros((B, 1, d), cd))
    x_prev = jnp.concatenate([prev0, x[:, :-1]], axis=1)
    xk = x + (x_prev - x) * p["mu_k"].astype(cd)
    xr = x + (x_prev - x) * p["mu_r"].astype(cd)
    kh = jnp.square(jax.nn.relu(jnp.dot(xk, p["w_k"].astype(cd))))
    y = jax.nn.sigmoid(jnp.dot(xr, p["w_r"].astype(cd))) * jnp.dot(
        kh, p["w_v"].astype(cd))
    return y, ({"shift": x[:, -1]} if make_cache else None)


def channelmix_decode(cfg, p, x1, cache):
    y, _ = channelmix_full(cfg, p,
                           x1, cache={"shift": cache["shift"]},
                           make_cache=False)
    return y, {"shift": x1[:, 0].astype(dt(cfg.compute_dtype))}
