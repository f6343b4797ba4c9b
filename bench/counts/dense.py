"""Operation counts of the dense decoder family (``reference/dense.py``):
grouped-query attention and SwiGLU in every layer."""


def layer_matmul_params(c: dict, layer: int) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the three SwiGLU matrices."""
    d = c["d_model"]
    hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["d_head"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * c["d_ff"]
