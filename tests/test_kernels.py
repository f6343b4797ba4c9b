"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode).

Tolerances: fp32 exact-ish (1e-5); bf16 inputs checked at 2e-2 (online
softmax reassociation); rwkv chunked-vs-sequential at 1e-3 fp32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # fall back to seeded-random sweeps
    from _hyp_fallback import given, settings, strategies as st

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# vecadd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [128, 16384, 50000])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_vecadd(n, dtype):
    from repro.kernels.vecadd.ops import vecadd_op
    from repro.kernels.vecadd.ref import vecadd_ref
    x = jax.random.normal(KEY, (n,), jnp.dtype(dtype))
    y = jax.random.normal(jax.random.fold_in(KEY, 1), (n,), jnp.dtype(dtype))
    np.testing.assert_allclose(np.asarray(vecadd_op(x, y), np.float32),
                               np.asarray(vecadd_ref(x, y), np.float32))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=1, max_value=5000))
def test_vecadd_property(n):
    from repro.kernels.vecadd.ops import vecadd_op
    x = jnp.arange(n, dtype=jnp.float32)
    y = jnp.ones((n,), jnp.float32)
    out = vecadd_op(x, y, block=1024)
    np.testing.assert_allclose(np.asarray(out), np.arange(n) + 1.0)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (256, 512, 128),
                                   (100, 300, 50), (33, 17, 9)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_matmul(m, k, n, dtype):
    from repro.kernels.matmul.ops import matmul_op
    from repro.kernels.matmul.ref import matmul_ref
    x = jax.random.normal(KEY, (m, k), jnp.dtype(dtype))
    y = jax.random.normal(jax.random.fold_in(KEY, 2), (k, n),
                          jnp.dtype(dtype))
    got = np.asarray(matmul_op(x, y), np.float32)
    want = np.asarray(matmul_ref(x, y), np.float32)
    tol = 1e-5 if dtype == "float32" else 2e-1
    np.testing.assert_allclose(got, want, atol=tol * np.sqrt(k), rtol=tol)


# ---------------------------------------------------------------------------
# sobel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,w", [(64, 128), (100, 180), (256, 256)])
def test_sobel(h, w):
    from repro.kernels.sobel.ops import sobel_op
    from repro.kernels.sobel.ref import sobel_ref
    img = jax.random.normal(KEY, (h, w), jnp.float32)
    np.testing.assert_allclose(np.asarray(sobel_op(img)),
                               np.asarray(sobel_ref(img)),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _qkv(B, S, Hq, Hkv, hd, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("S,Hq,Hkv,window",
                         [(128, 4, 4, 0), (128, 4, 2, 0), (256, 8, 1, 0),
                          (128, 4, 2, 32), (96, 2, 2, 0)])
def test_flash_attention(S, Hq, Hkv, window):
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q, k, v = _qkv(2, S, Hq, Hkv, 64)
    got = flash_attention_op(q, k, v, causal=True, window=window)
    want = flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
        window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q, k, v = _qkv(1, 128, 4, 4, 64, jnp.bfloat16)
    got = np.asarray(flash_attention_op(q, k, v), np.float32)
    want = np.asarray(flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3), np.float32)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


def test_flash_attention_grad_matches_ref():
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q, k, v = _qkv(1, 64, 2, 2, 32)

    def loss_kernel(q, k, v):
        return flash_attention_op(q, k, v).sum()

    def loss_ref(q, k, v):
        return flash_attention_ref(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3)).sum()

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C,Hq,Hkv,pos,window",
                         [(256, 4, 2, 100, 0), (256, 4, 2, 300, 0),
                          (128, 8, 1, 127, 0), (256, 4, 4, 300, 64)])
def test_decode_attention(C, Hq, Hkv, pos, window):
    from repro.kernels.decode_attention.ops import decode_attention_op
    from repro.kernels.decode_attention.ref import decode_attention_ref
    ks = jax.random.split(KEY, 3)
    B, hd = 2, 64
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, C, Hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, C, Hkv, hd), jnp.float32)
    got = decode_attention_op(q, kc, vc, pos, window=window)
    want = decode_attention_ref(
        q.transpose(0, 2, 1, 3), kc.transpose(0, 2, 1, 3),
        vc.transpose(0, 2, 1, 3), jnp.int32(pos),
        window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# Paged cases: (B, Hq, Hkv, hd, ps, nb, lengths). "small" is the first
# shape; "g2-hd128" has 32 pages per compute block (f32 pages of 32 KiB)
# over a 40-block table, so its blocks do not divide the table, and lengths
# 0, 1, ps-1, ps, ps+1, one inside the first block, one inside the second
# and the full table; "g1-hd64" packs two 64-wide heads per 128-lane row.
_PAGED_SHAPES = {
    "small": (3, 4, 2, 32, 8, 4, [13, 0, 32]),
    "g2-hd128": (8, 8, 4, 128, 16, 40, [0, 1, 15, 16, 17, 300, 520, 640]),
    "g1-hd64": (8, 4, 4, 64, 16, 40, [0, 1, 15, 16, 17, 300, 520, 640]),
}
_PAGED_CASES = [
    pytest.param(0, "small", id="0"),
    pytest.param(5, "small", id="5"),
    *(pytest.param(w, shape, id=f"{shape}-w{w}")
      for shape in ("g2-hd128", "g1-hd64") for w in (0, 203)),
]


def _paged_case(key, shape, window, *, seed, fused=False):
    """q, a scattered page pool (clean, and with NaN in every row that no
    valid token reads: partial pages' tails, rows before the window, pages
    only padded table entries point to and, fused, the newest token's row),
    block tables and lengths. Fused lengths count the newest token."""
    B, Hq, Hkv, hd, ps, nb, lens = _PAGED_SHAPES[shape]
    if fused and shape == "small":
        lens = [14, 0, 32]
    P = B * nb + 2
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (P, ps, Hkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (P, ps, Hkv, hd), jnp.float32)
    bt = np.random.default_rng(seed).permutation(P)[:B * nb].reshape(B, nb)
    read = np.zeros((P, ps), bool)
    for b, n in enumerate(lens):
        lo = max(n - window, 0) if window else 0
        for t in range(lo, n - fused):
            read[bt[b, t // ps], t % ps] = True
    poison = lambda x: jnp.where(jnp.asarray(read)[..., None, None], x,
                                 jnp.nan)
    return (q, (kp, vp), (poison(kp), poison(vp)),
            jnp.asarray(bt.astype(np.int32)),
            jnp.asarray(np.array(lens, np.int32)))


@pytest.mark.parametrize("window,shape", _PAGED_CASES)
def test_paged_decode_attention_matches_ref(window, shape):
    """Paged kernel vs its gather oracle over a scattered (permuted)
    page pool, including a dead slot (length 0 → zeros). The kernel reads
    a pool with NaN in every row no valid token reads; its output must be
    finite and match the oracle's over the clean pool."""
    from repro.kernels.decode_attention.ops import decode_attention_op
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    q, (kp, vp), (kx, vx), bt, lens = _paged_case(KEY, shape, window,
                                                  seed=0)
    got = decode_attention_op(q, kx, vx, lens, window=window,
                              block_tables=bt)
    want = paged_decode_attention_ref(
        q.transpose(0, 2, 1, 3), kp, vp, lens, bt,
        window=window).transpose(0, 2, 1, 3)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for b in np.flatnonzero(np.asarray(lens) == 0):
        assert bool(jnp.all(got[b] == 0))      # dead slot stays zero


def test_paged_matches_contiguous_decode_attention():
    """The acceptance bound: paged decode attention over pages built
    from a contiguous cache matches the contiguous kernel ≤ 1e-3 (both
    in interpret mode on CPU)."""
    from repro.kernels.decode_attention.ops import decode_attention_op
    ks = jax.random.split(KEY, 3)
    B, Hq, Hkv, hd, ps, nb = 2, 4, 2, 64, 16, 8
    C, pos = nb * ps, 100
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, C, Hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, C, Hkv, hd), jnp.float32)
    contiguous = decode_attention_op(q, kc, vc, pos)
    kp = kc.reshape(B * nb, ps, Hkv, hd)
    vp = vc.reshape(B * nb, ps, Hkv, hd)
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    lens = jnp.full((B,), pos + 1, jnp.int32)
    paged = decode_attention_op(q, kp, vp, lens, block_tables=bt)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(contiguous),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("window,shape", [
    pytest.param(0, "small", id="0"), pytest.param(6, "small", id="6"),
    *_PAGED_CASES[2:]])
def test_fused_decode_matches_scatter_then_paged(window, shape):
    """The fused serving step (new-token K/V substituted in-register)
    must match scatter-then-paged-attention ≤ 1e-3, and the XLA
    fallback must agree on the *same* inputs. Includes a dead slot
    (length 0 → zeros). The fused kernel reads a pool with NaN in every
    row no valid token reads, the newest token's row among them."""
    from repro.kernels.decode_attention.ops import (
        decode_attention_op, fused_decode_step_op,
        fused_paged_attention_xla)
    q, (kp, vp), (kx, vx), bt, lens = _paged_case(
        jax.random.fold_in(KEY, 1), shape, window, seed=1, fused=True)
    B, (Hkv, hd) = q.shape[0], kp.shape[2:]
    ks = jax.random.split(jax.random.fold_in(KEY, 2))
    kn = jax.random.normal(ks[0], (B, 1, Hkv, hd), jnp.float32)
    vn = jax.random.normal(ks[1], (B, 1, Hkv, hd), jnp.float32)

    fused = fused_decode_step_op(q, kn, vn, kx, vx, lens, bt,
                                 window=window)
    # the XLA fallback speaks kernel layout (B,H,1,hd)
    xla = fused_paged_attention_xla(
        q.transpose(0, 2, 1, 3), kn.transpose(0, 2, 1, 3),
        vn.transpose(0, 2, 1, 3), kp, vp, lens, bt,
        window=window).transpose(0, 2, 1, 3)

    # oracle: scatter the new token into the pool, then plain paged
    kp2, vp2 = np.array(kp), np.array(vp)
    ps = kp.shape[1]
    for b, L in enumerate(np.asarray(lens)):
        if L == 0:
            continue
        pg, off = int(bt[b, (L - 1) // ps]), (L - 1) % ps
        kp2[pg, off] = np.asarray(kn)[b, 0]
        vp2[pg, off] = np.asarray(vn)[b, 0]
    want = decode_attention_op(q, jnp.asarray(kp2), jnp.asarray(vp2),
                               lens, window=window, block_tables=bt)
    assert bool(jnp.all(jnp.isfinite(fused)))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want),
                               atol=1e-3, rtol=1e-3)
    for b in np.flatnonzero(np.asarray(lens) == 0):
        assert bool(jnp.all(fused[b] == 0))    # dead slot stays zero
        assert bool(jnp.all(xla[b] == 0))


def test_fused_decode_new_token_only():
    """Length 1: attention over just the in-register new token must
    return v_new exactly (softmax over one key), never touch the pool."""
    from repro.kernels.decode_attention.ops import fused_decode_step_op
    ks = jax.random.split(KEY, 3)
    B, Hq, Hkv, hd, ps, nb = 2, 2, 2, 16, 4, 2
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.float32)
    kn = jax.random.normal(ks[1], (B, 1, Hkv, hd), jnp.float32)
    vn = jax.random.normal(ks[2], (B, 1, Hkv, hd), jnp.float32)
    # poison the pool with NaNs in *masked* positions — the online
    # softmax must never mix them in
    kp = jnp.zeros((B * nb, ps, Hkv, hd), jnp.float32)
    vp = jnp.full((B * nb, ps, Hkv, hd), 7.25, jnp.float32)
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    lens = jnp.ones((B,), jnp.int32)
    out = fused_decode_step_op(q, kn, vn, kp, vp, lens, bt)
    want = jnp.broadcast_to(vn, (B, 1, Hq, hd))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("V", [512, 1000])
def test_sample_tokens_matches_argmax(V):
    """On-device sampler vs XLA fallback vs host np.argmax: greedy rows
    (T=0) and Gumbel rows (T>0) must agree exactly — argmax of
    logits + noise·T is scale-invariant, so one formula covers both."""
    from repro.kernels.decode_attention.ops import (sample_tokens_op,
                                                    sample_tokens_xla)
    ks = jax.random.split(KEY, 2)
    B = 4
    logits = jax.random.normal(ks[0], (B, V), jnp.float32) * 3.0
    noise = jax.random.gumbel(ks[1], (B, V), jnp.float32)
    temps = jnp.asarray([0.0, 0.8, 0.0, 1.5], jnp.float32)
    got = sample_tokens_op(logits, temps, noise)
    xla = sample_tokens_xla(logits, temps, noise)
    want = np.argmax(np.asarray(logits)
                     + np.asarray(noise) * np.asarray(temps)[:, None],
                     axis=-1).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(xla), want)


def test_sample_tokens_tie_keeps_first():
    """Exact ties must resolve to the lowest index (np.argmax
    semantics), including ties that straddle vocab blocks."""
    from repro.kernels.decode_attention.ops import (sample_tokens_op,
                                                    sample_tokens_xla)
    V = 4096                      # two 2048-wide blocks
    logits = np.zeros((2, V), np.float32)
    logits[0, [100, 3000]] = 5.0  # tie across blocks → keep 100
    logits[1, [2050, 2051]] = 2.0  # tie inside block 2 → keep 2050
    temps = jnp.zeros((2,), jnp.float32)
    noise = jnp.zeros((2, V), jnp.float32)
    want = np.array([100, 2050], np.int32)
    got = sample_tokens_op(jnp.asarray(logits), temps, noise)
    xla = sample_tokens_xla(jnp.asarray(logits), temps, noise)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(xla), want)


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,D", [(64, 128), (100, 300), (256, 512)])
def test_rglru_scan(S, D):
    from repro.kernels.rglru_scan.ops import rglru_scan_op
    from repro.kernels.rglru_scan.ref import rglru_scan_ref
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (2, S, D), jnp.float32, 0.5, 0.999)
    b = jax.random.normal(ks[1], (2, S, D), jnp.float32)
    h0 = jax.random.normal(ks[2], (2, D), jnp.float32)
    np.testing.assert_allclose(np.asarray(rglru_scan_op(a, b, h0)),
                               np.asarray(rglru_scan_ref(a, b, h0)),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,K,chunk", [(64, 32, 32), (70, 32, 16),
                                       (128, 64, 32)])
def test_rwkv6_wkv(S, K, chunk):
    from repro.kernels.rwkv6_wkv.ops import rwkv6_wkv_op
    from repro.kernels.rwkv6_wkv.ref import rwkv6_wkv_ref
    ks = jax.random.split(KEY, 6)
    B, H = 2, 2
    r = jax.random.normal(ks[0], (B, H, S, K), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, K), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, K), jnp.float32)
    lw = -jnp.exp(jax.random.normal(ks[3], (B, H, S, K), jnp.float32))
    u = jax.random.normal(ks[4], (H, K), jnp.float32)
    s0 = jax.random.normal(ks[5], (B, H, K, K), jnp.float32)
    o, sf = rwkv6_wkv_op(r, k, v, lw, u, s0, chunk=chunk)
    oref, sfref = rwkv6_wkv_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sfref),
                               atol=2e-3, rtol=2e-3)


def test_rwkv6_wkv_extreme_decay_is_safe():
    """Fast-decay channels must underflow to exact zero, never NaN/inf."""
    from repro.kernels.rwkv6_wkv.ops import rwkv6_wkv_op
    B, H, S, K = 1, 1, 64, 32
    r = jnp.ones((B, H, S, K))
    k = jnp.ones((B, H, S, K))
    v = jnp.ones((B, H, S, K))
    lw = jnp.full((B, H, S, K), -50.0)       # decay ~e^-50 per step
    u = jnp.zeros((H, K))
    s0 = jnp.zeros((B, H, K, K))
    o, sf = rwkv6_wkv_op(r, k, v, lw, u, s0)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(sf)).all()
