"""Decode attention — one query token against a deep KV cache.

Flash-decoding-style: the KV cache is streamed through VMEM in blocks with
an online-softmax carry; the (1, hd) query stays VMEM-resident for the
whole sweep. Validity masking (ring caches that are not yet full) comes
from a scalar `pos` operand placed in SMEM. Decode is HBM-bandwidth-bound:
the kernel's roofline is the cache-read stream, which is why the block
size is large (maximize DMA efficiency, compute is negligible).

Two cache layouts share the online-softmax body:

* ``decode_attention``       — contiguous per-slot ring caches
  (B, Hkv, C, hd) with one shared scalar ``pos`` (the reference).
* ``paged_decode_attention`` — a shared physical page pool
  (num_pages, page_size, Hkv, hd) plus per-slot block tables and lengths.
  Both the block table and the lengths vector are scalar-prefetched into
  SMEM so each grid step's page index is known before the body runs — the
  page DMA address is computed from the table, which is what makes the
  virtual→physical walk free. Pages are linear (token t of slot b lives
  at page ``bt[b, t // ps]``, offset ``t % ps``; no ring), so validity is
  a simple ``t < lengths[b]`` mask and out-of-table grid steps (padded
  block-table entries) mask to -inf and contribute nothing.
  ``page_size`` should be a multiple of the 128-lane tile on real TPU;
  small pages are fine in interpret mode.

Fused serving-step kernels (PR 7):

* ``fused_paged_decode_attention`` — the paged sweep with the *new*
  token's K/V fused in-register: the freshly projected (B, Hkv, 1, hd)
  K/V rides in VMEM and is substituted for pool row ``lengths-1`` during
  the sweep, so decode attention no longer serializes behind the HBM
  scatter that persists it (the scatter still runs, concurrently, to
  keep the pool current for the *next* step — but this step never reads
  the page it just wrote).
* ``sample_tokens`` — on-device argmax/Gumbel-max sampling over the
  final logits. ``argmax(logits + g·T)`` with Gumbel noise ``g`` equals
  softmax sampling at temperature ``T`` and degrades to greedy argmax at
  ``T = 0``, so one kernel covers both and only (B,) token ids ever
  leave the device (the old ``_sample`` round-tripped (B, V) logits to
  host every step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BKV = 1024
_NEG = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, bkv, nk, window, capacity):
    kidx = pl.program_id(1)

    @pl.when(kidx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                   # (1, hd)
    k = k_ref[0, 0]                                   # (bkv, hd)
    v = v_ref[0, 0]
    pos = pos_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    slot = kidx * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
    valid = (slot <= pos) | (pos >= capacity)
    if window > 0:
        cur = jnp.mod(pos, capacity)
        age = jnp.mod(cur - slot, capacity)
        valid &= age < window
    s = jnp.where(valid, s, _NEG)
    m_prev = m_ref[:1, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(kidx == nk - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:1, :1], 1e-30)).astype(
                           o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "bkv"))
def decode_attention(q, k, v, pos, *, window=0, interpret=False, bkv=BKV):
    """q: (B,Hq,1,hd); k/v: (B,Hkv,C,hd) ring caches; pos: () int32."""
    B, Hq, _, hd = q.shape
    _, Hkv, C, _ = k.shape
    G = Hq // Hkv
    bkv = min(bkv, C)
    assert C % bkv == 0
    nk = C // bkv
    grid = (B * Hq, nk)
    pos_arr = jnp.broadcast_to(pos[None].astype(jnp.int32), (1,))

    kernel = functools.partial(_kernel, scale=hd ** -0.5, bkv=bkv, nk=nk,
                               window=window, capacity=C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd),
                         lambda g, j, pos: (g // Hq, g % Hq, 0, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda g, j, pos: (g // Hq, (g % Hq) // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda g, j, pos: (g // Hq, (g % Hq) // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda g, j, pos: (g // Hq, g % Hq, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, hd), jnp.float32),
                        pltpu.VMEM((1, 128), jnp.float32),
                        pltpu.VMEM((1, 128), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(pos_arr, q, k, v)


# ===========================================================================
# Paged variant: block-table walk over a shared physical page pool
# ===========================================================================
#
# One grid step reads one whole page, all KV heads of it: a
# (page_size, Hkv, hd) block whose last two dims are the pool's own, which
# is the block shape the TPU compiler accepts (a single-head (ps, 1, hd)
# block is refused). The query heads of each KV group are then handled
# together, as G rows of one (G, ps) score tile.


def _paged_kernel(len_ref, bt_ref, q_ref, *refs, scale, ps, nb, window,
                  hkv, g, fused):
    if fused:
        kn_ref, vn_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)                              # logical block index

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]                   # fused: includes the new token
    tok = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    valid = tok < length                              # linear, no ring
    if window > 0:
        valid &= tok >= length - window
    if fused:
        # the new token lives at logical index length-1 but is NOT in the
        # pool yet: substitute its VMEM-resident row into the sweep
        is_new = (j * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
                  == length - 1)
    for h in range(hkv):
        rows = slice(h * g, (h + 1) * g)
        q = q_ref[0, rows, :]                         # (G, hd)
        k = k_ref[0, :, h, :]                         # (ps, hd)
        v = v_ref[0, :, h, :]
        if fused:
            k = jnp.where(is_new, kn_ref[0, h:h + 1, :], k)
            v = jnp.where(is_new, vn_ref[0, h:h + 1, :], v)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, _NEG)                 # (G, ps)
        m_prev = m_ref[rows, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[rows, :] = l_ref[rows, :] * alpha + p.sum(-1, keepdims=True)
        acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[rows, :] = jnp.broadcast_to(m_new, (g, m_ref.shape[1]))

    @pl.when(j == nb - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _paged_call(q, new_kv, k_pages, v_pages, lengths, block_tables, *,
                window, interpret):
    """Shared pallas_call of the paged and fused kernels. ``new_kv`` is
    ``(k_new, v_new)`` (B, Hkv, 1, hd) for the fused step, else None."""
    B, Hq, _, hd = q.shape
    _, ps, Hkv, _ = k_pages.shape
    nb = block_tables.shape[1]
    fused = new_kv is not None
    head_spec = lambda h: pl.BlockSpec((1, h, hd),
                                       lambda b, j, lens, bt: (b, 0, 0))
    page_spec = pl.BlockSpec((1, ps, Hkv, hd),
                             lambda b, j, lens, bt: (bt[b, j], 0, 0, 0))
    in_specs = [head_spec(Hq)]
    operands = [q[:, :, 0]]
    if fused:
        in_specs += [head_spec(Hkv), head_spec(Hkv)]
        operands += [x[:, :, 0] for x in new_kv]
    in_specs += [page_spec, page_spec]
    operands += [k_pages, v_pages]
    kernel = functools.partial(_paged_kernel, scale=hd ** -0.5, ps=ps,
                               nb=nb, window=window, hkv=Hkv, g=Hq // Hkv,
                               fused=fused)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=head_spec(Hq),
        scratch_shapes=[pltpu.VMEM((Hq, hd), jnp.float32),
                        pltpu.VMEM((Hq, 128), jnp.float32),
                        pltpu.VMEM((Hq, 128), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), *operands)
    return out[:, :, None]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, lengths, block_tables, *,
                           window=0, interpret=False):
    """q: (B,Hq,1,hd); k/v pages: (P, page_size, Hkv, hd) shared pool;
    lengths: (B,) int32 valid-token counts (0 = dead slot → zero out);
    block_tables: (B, nb) int32 logical block → physical page (pad with
    any in-range page; padded entries are masked by ``lengths``)."""
    return _paged_call(q, None, k_pages, v_pages, lengths, block_tables,
                       window=window, interpret=interpret)


# ===========================================================================
# Fused serving step: new-token KV in-register + paged sweep
# ===========================================================================


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def fused_paged_decode_attention(q, k_new, v_new, k_pages, v_pages,
                                 lengths, block_tables, *, window=0,
                                 interpret=False):
    """Paged decode attention with the new token's K/V fused in-register.

    q: (B,Hq,1,hd); k_new/v_new: (B,Hkv,1,hd) the step's freshly
    projected (roped) K/V, logically at index ``lengths-1``; k/v pages:
    (P, page_size, Hkv, hd) shared pool NOT yet containing the new
    token; lengths: (B,) int32 valid counts *including* the new token
    (0 = dead slot → zero output, its k_new/v_new ignored);
    block_tables: (B, nb) int32. The caller persists k_new/v_new to the
    pool separately — this kernel never reads the page being written.
    """
    return _paged_call(q, (k_new, v_new), k_pages, v_pages, lengths,
                       block_tables, window=window, interpret=interpret)


# ===========================================================================
# On-device sampling: argmax / Gumbel-max over the final logits
# ===========================================================================


def _sample_kernel(temp_ref, s_ref, n_ref, tok_ref, m_ref, i_ref, *,
                   bv, nv):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        i_ref[...] = jnp.zeros_like(i_ref)

    # argmax(logits + g·T): Gumbel-max softmax sampling at temperature T
    # (argmax is scale-invariant: argmax(l/T + g) == argmax(l + g·T)),
    # greedy argmax at T = 0 — one formula for both
    s = s_ref[...] + n_ref[...] * temp_ref[...]       # (tb, bv)
    bmax = s.max(axis=-1, keepdims=True)              # (tb, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # first column attaining the block max (matches np.argmax ties)
    bidx = jnp.min(jnp.where(s == bmax, col, bv),
                   axis=-1, keepdims=True) + j * bv
    better = bmax > m_ref[...]                        # strict: keep first
    m_ref[...] = jnp.where(better, bmax, m_ref[...])
    i_ref[...] = jnp.where(better, bidx, i_ref[...])

    @pl.when(j == nv - 1)
    def _flush():
        tok_ref[...] = i_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "bv"))
def sample_tokens(logits, temps, noise, *, interpret=False, bv=2048):
    """logits (B, V) fp32; temps (B,) fp32 (0 = greedy); noise (B, V)
    Gumbel draws (ignored where temps == 0). → (B,) int32 token ids.

    Rows go in tiles of 8 (or all B when B is not a multiple of 8), the
    vocabulary in ``bv``-wide blocks; a vocabulary that is not a multiple
    of ``bv`` is padded with -inf logits, which never win."""
    B, V = logits.shape
    bv = min(bv, V)
    tb = 8 if B % 8 == 0 else B
    logits = logits.astype(jnp.float32)
    noise = noise.astype(jnp.float32)
    pad = -V % bv
    if pad:
        logits = jnp.pad(logits, ((0, 0), (0, pad)), constant_values=_NEG)
        noise = jnp.pad(noise, ((0, 0), (0, pad)))
    nv = (V + pad) // bv
    kernel = functools.partial(_sample_kernel, bv=bv, nv=nv)
    row_spec = pl.BlockSpec((tb, 1), lambda i, j: (i, 0))
    vocab_spec = pl.BlockSpec((tb, bv), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel,
        grid=(B // tb, nv),
        in_specs=[row_spec, vocab_spec, vocab_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tb, 1), jnp.float32),
                        pltpu.VMEM((tb, 1), jnp.int32)],
        interpret=interpret,
    )(temps.astype(jnp.float32)[:, None], logits, noise)[:, 0]
