"""RWKV-6 "Finch" (arXiv:2404.05892): LayerNorm, time-mix with
data-dependent token shift (LoRA), data-dependent per-channel decay and
the WKV recurrence run token by token, per-head GroupNorm, SiLU gate;
channel-mix with token shift, squared ReLU and a sigmoid receptance;
final LayerNorm and LM head.

Departures from the paper, which follow the program under test and are
part of the model it serves: sinusoidal absolute positions are added to
the token embeddings, and there is no LayerNorm after the embedding.
The LoRA ranks are the configuration's ``rwkv_mix_lora_rank`` and
``rwkv_decay_lora_rank``; weights of other ranks fail to reshape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layernorm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def sinusoid(pos, d):
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def shifted(x):
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def wkv(r, k, v, w, u):
    """Token-by-token recurrence. r,k,v,w (S, H, K); u (H, K) →
    o (S, H, K). State s (H, K, V): o = r·(s + u k vᵀ), s ← w s + k vᵀ."""
    H, K = u.shape

    def step(s, inp):
        rt, kt, vt, wt = inp
        kv = kt[:, :, None] * vt[:, None, :]
        o = jnp.einsum("hk,hkv->hv", rt, s + u[:, :, None] * kv,
                       precision=jax.lax.Precision.HIGHEST)
        return wt[:, :, None] * s + kv, o
    _, o = jax.lax.scan(step, jnp.zeros((H, K, K), jnp.float32),
                        (r, k, v, w))
    return o


def time_mix(cfg, p, x, dot):
    S, d = x.shape
    K = cfg["rwkv_head_dim"]
    H = d // K
    dx = shifted(x) - x
    base = x + dx * p["mu_base"]
    lora = jnp.tanh(dot(base, p["mix_A"])).reshape(
        S, 5, cfg["rwkv_mix_lora_rank"])
    mixes = p["mu_rkvwg"][None] + jnp.einsum(
        "sfr,frd->sfd", lora, p["mix_B"], precision=jax.lax.Precision.HIGHEST)
    xr, xk, xv, xw, xg = (x + dx * mixes[:, i] for i in range(5))
    r = dot(xr, p["w_r"]).reshape(S, H, K)
    k = dot(xk, p["w_k"]).reshape(S, H, K)
    v = dot(xv, p["w_v"]).reshape(S, H, K)
    g = dot(xg, p["w_g"])
    decay_a = p["decay_A"].reshape(d, cfg["rwkv_decay_lora_rank"])
    ww = p["decay_base"] + dot(jnp.tanh(dot(xw, decay_a)), p["decay_B"])
    w = jnp.exp(-jnp.exp(ww)).reshape(S, H, K)
    o = wkv(r, k, v, w, p["bonus_u"])
    mu = o.mean(-1, keepdims=True)
    var = ((o - mu) ** 2).mean(-1, keepdims=True)
    o = ((o - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(S, d)
    o = o * p["ln_scale"] + p["ln_bias"]
    return dot(o * jax.nn.silu(g), p["w_o"])


def channel_mix(p, x, dot):
    dx = shifted(x) - x
    xk = x + dx * p["mu_k"]
    xr = x + dx * p["mu_r"]
    kh = jnp.square(jax.nn.relu(dot(xk, p["w_k"])))
    return jax.nn.sigmoid(dot(xr, p["w_r"])) * dot(kh, p["w_v"])


def logits(w, cfg, tokens, positions_out, dot):
    """tokens (S,) int32 → (len(positions_out), vocab) float32."""
    d, eps = cfg["d_model"], cfg["norm_eps"]
    x = w["tok_embed"][tokens].astype(jnp.float32)
    x = x + sinusoid(jnp.arange(tokens.shape[0]), d)
    layers = w["segments"][0][0]

    def body(x, p):
        h = layernorm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)
        x = x + time_mix(cfg, p["mixer"], h, dot)
        h = layernorm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
        return x + channel_mix(p["ffn"], h, dot), None
    x, _ = jax.lax.scan(body, x, layers)
    fn = w["final_norm"]
    x = layernorm(x[positions_out], fn["scale"], fn["bias"], eps)
    return dot(x, w["lm_head"])[:, :cfg["vocab"]]

