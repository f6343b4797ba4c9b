"""Operations and bytes from shapes: the yardstick of ``step_mfu`` and
of each kernel's roofline share. Counted from the configuration's
sizes, never from the compiled program, so a change to a kernel cannot
move its own yardstick. Matrix operands are bfloat16 (2 bytes), the
configuration's compute type; the paged KV pools are bfloat16 too, and
the WKV kernel's operands and state are float32.

A family's work per token and layer comes from its module
``counts/<reference>.py``, found by the configuration's ``reference``
key: ``layer_matmul_params(c, layer)``, the weights one token multiplies
through in that layer, and, where the family has any,
``layer_token_flops(c, layer)``, the FLOPs beyond those products and
attention. Attention is counted here, layer by layer, by the kind the
configuration's ``block_pattern`` gives it (default ``("attn",)``):
an ``attn`` layer reads every key, a ``swa`` layer the last ``window``
(0: every key), any other kind none.
"""
from __future__ import annotations

import numpy as np

from bench import found

BF16 = 2
F32 = 4


def family(c: dict):
    """The operation-count module of ``c``'s family."""
    return found.module("counts", c["reference"], c.get("bench", found.BENCH))


def layer_matmul_params(c: dict, layer: int = 0) -> int:
    """Weights one token multiplies through in layer ``layer``."""
    return family(c).layer_matmul_params(c, layer)


def layer_kinds(c: dict) -> list:
    """The mixer kind of each layer."""
    pattern = c.get("block_pattern", ("attn",))
    return [pattern[i % len(pattern)] for i in range(c["n_layers"])]


def attn_windows(c: dict) -> list:
    """One entry per attention layer: the keys it reads back from a
    query, counting the query's own (0: every key)."""
    window = {"attn": 0, "swa": c.get("window", 0)}
    return [window[k] for k in layer_kinds(c) if k in window]


def keys_seen(lengths, window: int) -> int:
    """Keys read by queries whose contexts, themselves included, are
    ``lengths`` long, in a layer that reads the last ``window`` (0:
    all)."""
    lengths = np.asarray(lengths, np.int64)
    if window:
        lengths = np.minimum(lengths, window)
    return int(lengths.sum())


def wkv_token_flops(c: dict) -> int:
    """One token through one layer's WKV recurrence, all heads:
    rᵀS (2K²), S ← w⊙S + k vᵀ (3K²), the bonus term (4K)."""
    K = c["rwkv_head_dim"]
    return (c["d_model"] // K) * (5 * K * K + 4 * K)


def _token_work(c: dict) -> tuple:
    """(weights one token multiplies through, its other FLOPs), summed
    over the layers."""
    fam = family(c)
    extra = getattr(fam, "layer_token_flops", lambda c, layer: 0)
    layers = range(c["n_layers"])
    return (sum(fam.layer_matmul_params(c, i) for i in layers),
            sum(extra(c, i) for i in layers))


def _attn_flops(c: dict, lengths) -> int:
    """QKᵀ and PV of queries with contexts ``lengths``, over the layers."""
    return sum(4 * c["n_heads"] * c["d_head"] * keys_seen(lengths, w)
               for w in attn_windows(c))


def chunk_flops(c: dict, length: int, start: int) -> float:
    """One prefill chunk of ``length`` tokens at ``start``; the LM head
    runs on its last token only."""
    params, other = _token_work(c)
    f = 2 * length * params + 2 * c["d_model"] * c["vocab"]
    f += _attn_flops(c, np.arange(start + 1, start + length + 1))
    f += length * other
    return float(f)


def decode_flops(c: dict, lengths) -> float:
    """One fused decode step over the live slots, whose context lengths
    (the new token included) are ``lengths``."""
    n = len(lengths)
    params, other = _token_work(c)
    f = 2 * n * (params + c["d_model"] * c["vocab"])
    f += _attn_flops(c, lengths) + n * other
    return float(f)


def paged_attn_cost(c: dict, lengths, window: int = 0) -> tuple:
    """(FLOPs, bytes) of one layer's fused paged decode attention over
    the live slots, in a layer that reads the last ``window`` keys (0:
    all; ``attn_windows`` gives each layer's): every key and value read
    once, the queries read and the outputs written once."""
    hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["d_head"]
    tokens = keys_seen(lengths, window)
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
