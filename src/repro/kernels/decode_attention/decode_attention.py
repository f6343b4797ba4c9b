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
  Both are scalar-prefetched into SMEM; the pools stay in HBM. One grid
  step walks every slot, and for each only the pages that hold its valid
  tokens: pages below ``ceil(lengths[b] / page_size)`` and, with a window,
  from the page holding ``lengths[b] - window`` on. It copies them a
  compute block of several pages at a time, one DMA per page addressed
  from the block table (the virtual→physical walk), into two VMEM buffers
  that alternate, so the next block's pages are in flight while this one
  is computed. The pages per block come from a page's bytes against a
  fixed VMEM budget (``PAGED_VMEM_BYTES``), at most the table's width.
  Pages are linear (token t of slot b lives at page ``bt[b, t // ps]``,
  offset ``t % ps``; no ring). Padded block-table entries are never read,
  a dead slot (length 0) copies nothing and returns zeros, and rows of a
  block that hold no valid token are masked in V as well as in the
  scores, so whatever they hold never reaches the output.

Fused serving-step kernels (PR 7):

* ``fused_paged_decode_attention`` — the paged sweep with the *new*
  token's K/V fused in-register: the freshly projected (B, Hkv, 1, hd)
  K/V rides in VMEM and starts each slot's online softmax in place of
  pool row ``lengths-1``, which the sweep never reads (it covers the
  pool's tokens below ``lengths-1``), so decode attention no longer
  serializes behind the HBM scatter that persists it (the scatter still
  runs, concurrently, to keep the pool current for the *next* step — but
  this step never reads the row it just wrote).
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
# The pools are not blocked (``memory_space=pl.ANY``): the kernel copies
# what it reads itself, seeing a page of (ps, Hkv, hd) as ``ps * rt`` rows
# of ``lanes`` (``rt`` rows per token, one per KV head, or ``hpr`` heads
# side by side in 128 lanes where hd is narrower). The whole sweep is one
# grid step. Its body walks the slots in order and, for each, only the
# compute blocks that hold the slot's valid pages: ``ppb`` pages per
# block, one DMA per page, its address read from the block table in SMEM.
# Two VMEM buffers alternate, so the next block's pages (at a slot's last
# block, the next slot's first) are in flight while this block is
# computed. Pages at or past ``ceil(len / ps)``, and with a window those
# before the page holding ``len - window``, are never copied; a dead slot
# copies nothing and its output stays zero.
#
# A block is computed for all heads at once: the (Hq, lanes) queries
# against all of the block's rows in one matmul, each query row keeping
# only the columns of its own KV head; then one matmul with V. Rows that
# were not copied hold whatever the buffer held before, so V is masked by
# row as well as the scores by column.

#: VMEM bytes for the double-buffered K and V blocks; the pages per compute
#: block follow from a page's bytes
PAGED_VMEM_BYTES = 4 * 1024 * 1024


def _pages_per_block(page_bytes, nb):
    """Pages per compute block: as many as the double-buffered K and V
    blocks fit in ``PAGED_VMEM_BYTES``, at most the table's width."""
    return max(1, min(nb, PAGED_VMEM_BYTES // (4 * page_bytes)))


def _heads_per_row(hkv, hd):
    """KV heads side by side in one row of the pool's view: a pool whose
    head is narrower than the 128 lanes cannot be sliced by page."""
    hpr = 128 // hd if hd < 128 and 128 % hd == 0 else 1
    return hpr if hkv % hpr == 0 else 1


def _paged_kernel(len_ref, bt_ref, q_ref, *refs, scale, ps, nb, ppb, rt,
                  gh, window, fused):
    if fused:
        kn_ref, vn_ref, *refs = refs
    (k_pool, v_pool, o_ref, kbuf, vbuf, sems, nxt_ref, acc_ref, m_ref,
     l_ref) = refs
    nslots = len_ref.shape[0]
    rows_pp = ps * rt                                 # rows per page
    # the pools as rows: reshaped here, since a reshape in the caller
    # makes XLA copy the layer's pool before every call
    k_pool, v_pool = (x.reshape(x.shape[0], rows_pp, x.shape[-1])
                    for x in (k_pool, v_pool))
    nrows = ppb * rows_pp                             # rows per block
    hq = q_ref.shape[1]
    # the KV head row (within a token) of each query row
    head_row = jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (hq, nrows), 0), gh)
    col_row = jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (hq, nrows), 1), rt)
    own_head = head_row == col_row                    # (Hq, nrows)

    def span(b):
        """Slot b's pool tokens [lo, hi), first page and block count. The
        fused step's newest token is not in the pool."""
        length = len_ref[b]
        lo = jnp.maximum(length - window, 0) if window > 0 else 0
        hi = jnp.maximum(length - 1, 0) if fused else length
        first = lo // ps
        nblk = jnp.maximum((hi + ps - 1) // ps - first + ppb - 1, 0) // ppb
        return lo, hi, first, nblk

    def page_copy(buf, hbm, phys, slot, i, sem):
        return pltpu.make_async_copy(
            hbm.at[phys], buf.at[slot, pl.ds(i * rows_pp, rows_pp)], sem)

    def pages(b, j):
        _, hi, first, _ = span(b)
        p0 = first + j * ppb
        return p0, jnp.minimum(ppb, (hi + ps - 1) // ps - p0)

    def start(b, j, slot):
        p0, n = pages(b, j)

        def one(i, c):
            phys = bt_ref[b * nb + p0 + i]
            page_copy(kbuf, k_pool, phys, slot, i, sems.at[0, slot]).start()
            page_copy(vbuf, v_pool, phys, slot, i, sems.at[1, slot]).start()
            return c

        jax.lax.fori_loop(0, n, one, 0)

    def wait(b, j, slot):
        def one(i, c):
            page_copy(kbuf, k_pool, 0, slot, 0, sems.at[0, slot]).wait()
            page_copy(vbuf, v_pool, 0, slot, 0, sems.at[1, slot]).wait()
            return c

        jax.lax.fori_loop(0, pages(b, j)[1], one, 0)

    def compute(b, j, slot):
        lo, hi, first, _ = span(b)
        base = (first + j * ppb) * ps                 # block's first token
        # token t < hi of this block <=> its rows lie below (hi - base)·rt
        col = jax.lax.broadcasted_iota(jnp.int32, (1, nrows), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (nrows, 1), 0)
        col_ok = col < (hi - base) * rt
        row_ok = row < (hi - base) * rt
        if window > 0:
            col_ok &= col >= (lo - base) * rt
            row_ok &= row >= (lo - base) * rt
        valid = own_head & col_ok                     # (Hq, nrows)
        k = kbuf[slot]                                # (nrows, lanes)
        v = jnp.where(row_ok, vbuf[slot], 0)          # never 0 * garbage
        s = jax.lax.dot_general(q_ref[b], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * scale, _NEG)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    def init(b):
        if not fused:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            return
        # the newest token (index length-1) is not in the pool: the
        # online softmax starts from it, its K/V rows picked per head
        pick = (jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (hq, rt), 0),
                            gh)
                == jax.lax.broadcasted_iota(jnp.int32, (hq, rt), 1))
        s_all = jax.lax.dot_general(q_ref[b], kn_ref[b],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s_new = jnp.sum(jnp.where(pick, s_all, 0.0), axis=-1,
                        keepdims=True) * scale        # (Hq, 1)
        vn = vn_ref[b]
        acc_ref[...] = jax.lax.dot_general(
            pick.astype(vn.dtype), vn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(s_new, m_ref.shape)
        l_ref[...] = jnp.ones_like(l_ref)

    # nxt[b]: the first slot after b with pool pages to copy (nslots where
    # there is none)
    def link(i, after):
        b = nslots - 1 - i
        nxt_ref[b] = after
        return jnp.where(span(b)[3] > 0, b, after)

    first = jax.lax.fori_loop(0, nslots, link, jnp.int32(nslots))
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(first < nslots)
    def _prime():
        start(first, 0, 0)

    def slot_body(b, cur):
        live = len_ref[b] > 0
        nblk = span(b)[3]

        @pl.when(live)
        def _init():
            init(b)

        def block(j, cur):
            more = j + 1 < nblk
            b_next = jnp.where(more, b, nxt_ref[b])

            @pl.when(b_next < nslots)
            def _prefetch():
                start(b_next, jnp.where(more, j + 1, 0), 1 - cur)

            wait(b, j, cur)
            compute(b, j, cur)
            return 1 - cur

        cur = jax.lax.fori_loop(0, nblk, block, cur)

        @pl.when(live)
        def _flush():
            o_ref[b] = (acc_ref[...]
                        / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
                            o_ref.dtype)

        return cur

    jax.lax.fori_loop(0, nslots, slot_body, jnp.int32(0))


def _paged_call(q, new_kv, k_pages, v_pages, lengths, block_tables, *,
                window, interpret):
    """Shared pallas_call of the paged and fused kernels. ``new_kv`` is
    ``(k_new, v_new)`` (B, Hkv, 1, hd) for the fused step, else None."""
    B, Hq, _, hd = q.shape
    P, ps, Hkv, _ = k_pages.shape
    G = Hq // Hkv
    nb = block_tables.shape[1]
    hpr = _heads_per_row(Hkv, hd)
    rt, lanes = Hkv // hpr, hpr * hd
    fused = new_kv is not None
    q = q[:, :, 0]
    if hpr > 1:
        # each query row holds its head's hd lanes of the row, zeros in
        # the lanes of the heads that share the row
        slot = jax.nn.one_hot(jnp.arange(Hq) // G % hpr, hpr, dtype=q.dtype)
        q = (q[:, :, None, :] * slot[None, :, :, None]).reshape(B, Hq, lanes)
    whole = lambda h: pl.BlockSpec((B, h, lanes),
                                   lambda i, lens, bt: (0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [whole(Hq)]
    operands = [q]
    if fused:
        in_specs += [whole(rt), whole(rt)]
        operands += [x.reshape(B, rt, lanes) for x in new_kv]
    in_specs += [pool, pool]
    operands += [x.reshape(P, ps, rt, lanes) for x in (k_pages, v_pages)]
    ppb = _pages_per_block(ps * Hkv * hd * k_pages.dtype.itemsize, nb)
    kernel = functools.partial(_paged_kernel, scale=hd ** -0.5, ps=ps,
                               nb=nb, ppb=ppb, rt=rt, gh=G * hpr,
                               window=window, fused=fused)
    buf = pltpu.VMEM((2, ppb * ps * rt, lanes), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=in_specs,
        out_specs=whole(Hq),
        scratch_shapes=[buf, buf,
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((B,), jnp.int32),
                        pltpu.VMEM((Hq, lanes), jnp.float32),
                        pltpu.VMEM((Hq, 128), jnp.float32),
                        pltpu.VMEM((Hq, 128), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, lanes), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32).reshape(-1),
      *operands)
    if hpr > 1:
        out = (out.reshape(B, Hq, hpr, hd) * slot[None, :, :, None]).sum(2)
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
