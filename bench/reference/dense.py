"""Dense decoder (InternLM2-style): RMSNorm, grouped-query attention with
rotary positions over the whole sequence, SwiGLU, final RMSNorm, LM
head. Follows arXiv:2403.17297; the rotary form rotates the two halves
of each head (the "rotate_half" convention of the released code)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (S, H, hd); pos (S,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def causal_attention(q, k, v, block=512):
    """Softmax attention of every query over the keys at or before it,
    in blocks of ``block`` queries (S a multiple of it, or below it)."""
    S, H, hd = q.shape
    b = min(block, S)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * b, b, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        ok = (i * b + jnp.arange(b))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                          precision=jax.lax.Precision.HIGHEST)
    return jax.lax.map(one, jnp.arange(S // b)).reshape(S, H, hd)


def layer(cfg, p, x, pos, dot):
    S, d = x.shape
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    a = p["mixer"]
    h = rmsnorm(x, p["norm1"]["scale"], cfg["norm_eps"])
    q = dot(h, a["wq"].reshape(d, hq * hd)).reshape(S, hq, hd)
    k = dot(h, a["wk"].reshape(d, hkv * hd)).reshape(S, hkv, hd)
    v = dot(h, a["wv"].reshape(d, hkv * hd)).reshape(S, hkv, hd)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    kv_of_head = np.arange(hq) // (hq // hkv)
    k, v = k[:, kv_of_head], v[:, kv_of_head]                  # (S, hq, hd)
    o = causal_attention(q, k, v)
    x = x + dot(o.reshape(S, hq * hd), a["wo"].reshape(hq * hd, d))
    f = p["ffn"]
    h = rmsnorm(x, p["norm2"]["scale"], cfg["norm_eps"])
    u = jax.nn.silu(dot(h, f["w_gate"])) * dot(h, f["w_up"])
    return x + dot(u, f["w_down"])


def logits(w, cfg, tokens, positions_out, dot):
    """tokens (S,) int32 → (len(positions_out), vocab) float32."""
    x = w["tok_embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    layers = w["segments"][0][0]

    def body(x, p):
        return layer(cfg, p, x, pos, dot), None
    x, _ = jax.lax.scan(body, x, layers)
    x = rmsnorm(x[positions_out], w["final_norm"]["scale"], cfg["norm_eps"])
    return dot(x, w["lm_head"])[:, :cfg["vocab"]]
