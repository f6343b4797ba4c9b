"""Operations and bytes from shapes: the yardstick of ``step_mfu`` and
of each kernel's roofline share. Counted from the configuration's
sizes, never from the compiled program, so a change to a kernel cannot
move its own yardstick. Matrix operands are bfloat16 (2 bytes), the
configuration's compute type; the paged KV pools are bfloat16 too, and
the WKV kernel's operands and state are float32.
"""
from __future__ import annotations

import numpy as np

BF16 = 2
F32 = 4


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d = c["d_model"]
    if c["reference"] == "dense":
        hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["d_head"]
        return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * c["d_ff"]
    if c["reference"] == "rwkv6":
        mix, decay = c["rwkv_mix_lora_rank"], c["rwkv_decay_lora_rank"]
        tmix = 5 * d * d + 2 * 5 * mix * d + 2 * decay * d
        cmix = 2 * d * c["d_ff"] + d * d
        return tmix + cmix
    raise KeyError(c["reference"])


def wkv_token_flops(c: dict) -> int:
    """One token through one layer's WKV recurrence, all heads:
    rᵀS (2K²), S ← w⊙S + k vᵀ (3K²), the bonus term (4K)."""
    K = c["rwkv_head_dim"]
    return (c["d_model"] // K) * (5 * K * K + 4 * K)


def _attn_flops(c: dict, keys_seen: int) -> int:
    """Mixer FLOPs beyond the matrix products, per layer: QKᵀ and PV over
    the keys each query sees (dense), or the recurrence (rwkv)."""
    if c["reference"] == "dense":
        return 4 * c["n_heads"] * c["d_head"] * keys_seen
    return 0


def chunk_flops(c: dict, length: int, start: int) -> float:
    """One prefill chunk of ``length`` tokens at ``start``; the LM head
    runs on its last token only."""
    L, d = c["n_layers"], c["d_model"]
    keys = length * start + length * (length + 1) // 2
    f = 2 * length * L * layer_matmul_params(c) + 2 * d * c["vocab"]
    f += L * _attn_flops(c, keys)
    if c["reference"] == "rwkv6":
        f += L * length * wkv_token_flops(c)
    return float(f)


def decode_flops(c: dict, lengths) -> float:
    """One fused decode step over the live slots, whose context lengths
    (the new token included) are ``lengths``."""
    lengths = np.asarray(lengths, np.int64)
    n, L, d = len(lengths), c["n_layers"], c["d_model"]
    f = 2 * n * (L * layer_matmul_params(c) + d * c["vocab"])
    f += L * _attn_flops(c, int(lengths.sum()))
    if c["reference"] == "rwkv6":
        f += L * n * wkv_token_flops(c)
    return float(f)


def paged_attn_cost(c: dict, lengths) -> tuple:
    """(FLOPs, bytes) of one layer's fused paged decode attention over
    the live slots: every valid key and value read once, the queries
    read and the outputs written once."""
    lengths = np.asarray(lengths, np.int64)
    hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["d_head"]
    tokens = int(lengths.sum())
    flops = 4 * hq * hd * tokens
    nbytes = 2 * hkv * hd * BF16 * tokens + 2 * len(lengths) * hq * hd * BF16
    return float(flops), float(nbytes)


def wkv_cost(c: dict, length: int) -> tuple:
    """(FLOPs, bytes) of one layer's WKV kernel over a chunk of
    ``length`` tokens: r, k, v, log w read and o written per token, the
    state read and written once, all float32."""
    K = c["rwkv_head_dim"]
    H = c["d_model"] // K
    flops = length * wkv_token_flops(c)
    nbytes = F32 * (5 * length * H * K + 2 * H * K * K + H * K)
    return float(flops), float(nbytes)


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """The least time the chip could take for the work."""
    return max(flops / peak_flops, nbytes / peak_bw)
