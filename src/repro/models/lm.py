"""LM assembly: layer specs → scan-segment layout → full/decode forward.

Scan-over-layers: homogeneous runs of layers are stacked (leading axis =
#periods) and applied with ``jax.lax.scan`` — keeps HLO size and compile
time O(1) in depth, which matters for the 61-layer 1T-param dry-run.
Heterogeneous patterns (Griffin's (rglru, rglru, swa), kimi's leading dense
layer) become [unroll prefix] + [scan over periods] + [unroll tail].

Caches: every layer kind owns a cache pytree —
  attn/swa : {"k","v"} ring buffers (B, C, Hkv, hd), slot = pos % C
  rglru    : {"h" (B,d) fp32, "conv" (B,3,d)}
  rwkv     : {"shift" (B,d), "s" (B,H,dk,dk) fp32}
  channelmix ffn: {"shift" (B,d)}
  cross-attn (enc-dec): {"k","v"} (B, S_enc, H, hd) — static after prefill

Paged serving state (``init_paged_state`` / ``apply_stack_decode`` with a
paged ctx): the attn/swa leaves become *shared physical page pools*
(num_pages, page_size, Hkv, hd) with per-slot block tables owned by the
serving engine's ``PagedKVCache`` — one block table shared by every
layer, one pool per layer (scan segments stack pools on a leading
periods axis, exactly like the contiguous caches). All non-attention
leaves keep their per-slot batch row layout. ``write_prefill_to_state``
scatters one freshly-prefilled request (a batch=1 contiguous cache) into
its leased pages / batch row without touching any other slot — the
O(newcomer) admission primitive.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models.layers import (add_abs_positions, apply_ffn, apply_norm,
                                 dt, embed_init, init_ffn, init_norm)

# ---------------------------------------------------------------------------
# Layer specs and layout
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LayerSpec:
    mixer: str                  # attn | swa | rglru | rwkv
    ffn: str                    # swiglu | gelu | moe | channelmix
    d_ff: int
    cross: bool = False


def layer_specs(cfg, cross=False) -> Tuple[LayerSpec, ...]:
    out = []
    for i in range(cfg.n_layers):
        mixer = cfg.layer_mixer(i)
        ffn, d_ff = cfg.ffn_kind, cfg.d_ff
        if cfg.ffn_kind == "moe" and i < cfg.moe.first_dense_layers:
            ffn, d_ff = "swiglu", cfg.moe.dense_d_ff
        out.append(LayerSpec(mixer, ffn, d_ff, cross))
    return tuple(out)


def build_layout(cfg, specs):
    """→ list of ("unroll", specs_tuple) / ("scan", period_specs, n)."""
    n = len(specs)
    if not cfg.sharding.scan_layers:
        return [("unroll", specs)]
    prefix = cfg.moe.first_dense_layers if cfg.ffn_kind == "moe" else 0
    p = len(cfg.block_pattern)
    body = specs[prefix:]
    n_scan, tail = divmod(len(body), p)
    period = body[:p]
    for j in range(n_scan):                      # verify true periodicity
        assert body[j * p:(j + 1) * p] == period, "non-periodic stack"
    layout = []
    if prefix:
        layout.append(("unroll", specs[:prefix]))
    if n_scan:
        layout.append(("scan", period, n_scan))
    if tail:
        layout.append(("unroll", body[n_scan * p:]))
    return layout


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------


def init_layer(cfg, key, spec: LayerSpec):
    ks = jax.random.split(key, 6)
    p = {"norm1": init_norm(cfg), "norm2": init_norm(cfg)}
    if spec.mixer in ("attn", "swa"):
        p["mixer"] = attn.init_attn(cfg, ks[0])
    elif spec.mixer == "rglru":
        p["mixer"] = rec.init_rglru(cfg, ks[0])
    elif spec.mixer == "rwkv":
        p["mixer"] = rec.init_rwkv_tmix(cfg, ks[0])
    else:
        raise ValueError(spec.mixer)
    if spec.cross:
        p["norm_cross"] = init_norm(cfg)
        p["cross"] = attn.init_attn(cfg, ks[1])
    if spec.ffn == "moe":
        p["ffn"] = moe_mod.init_moe(cfg, ks[2])
    elif spec.ffn == "channelmix":
        p["ffn"] = rec.init_channelmix(cfg, ks[2])
    else:
        p["ffn"] = init_ffn(cfg, ks[2], kind=spec.ffn, d_ff=spec.d_ff)
    return p


def init_layer_cache(cfg, spec: LayerSpec, batch, capacity, enc_len=0):
    """Zero cache pytree for one layer (concrete; eval_shape-able)."""
    cd = dt(cfg.compute_dtype)
    c = {}
    if spec.mixer in ("attn", "swa"):
        C = capacity if spec.mixer == "attn" else min(cfg.window, capacity)
        c["mixer"] = {
            "k": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.d_head), cd),
            "v": jnp.zeros((batch, C, cfg.n_kv_heads, cfg.d_head), cd)}
    elif spec.mixer == "rglru":
        c["mixer"] = {"h": jnp.zeros((batch, cfg.d_model), jnp.float32),
                      "conv": jnp.zeros(
                          (batch, rec.RG_CONV_WIDTH - 1, cfg.d_model), cd)}
    elif spec.mixer == "rwkv":
        dk = cfg.rwkv_head_dim
        H = cfg.d_model // dk
        c["mixer"] = {"shift": jnp.zeros((batch, cfg.d_model), cd),
                      "s": jnp.zeros((batch, H, dk, dk), jnp.float32)}
    if spec.cross:
        c["cross"] = {
            "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.d_head), cd),
            "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.d_head), cd)}
    if spec.ffn == "channelmix":
        c["ffn"] = {"shift": jnp.zeros((batch, cfg.d_model), cd)}
    return c


def init_layer_paged(cfg, spec: LayerSpec, batch, num_pages, page_size,
                     enc_len=0):
    """Like ``init_layer_cache`` but attn/swa leaves are shared page
    pools (no batch dim — slots own *pages*, not rows)."""
    cd = dt(cfg.compute_dtype)
    c = init_layer_cache(cfg, spec, batch, 1, enc_len=enc_len)
    if spec.mixer in ("attn", "swa"):
        c["mixer"] = {
            "k": jnp.zeros((num_pages, page_size, cfg.n_kv_heads,
                            cfg.d_head), cd),
            "v": jnp.zeros((num_pages, page_size, cfg.n_kv_heads,
                            cfg.d_head), cd)}
    return c


# ---------------------------------------------------------------------------
# Per-layer apply
# ---------------------------------------------------------------------------


def apply_layer_full(cfg, spec, p, x, ctx, cache=None):
    """Full-sequence layer. Returns (x', new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg, p["norm1"], x)
    mk = ctx["make_cache"]
    if spec.mixer in ("attn", "swa"):
        window = cfg.window if spec.mixer == "swa" else 0
        y, mcache = attn.attn_full(
            cfg, p["mixer"], h, causal=ctx["causal"], window=window,
            positions=ctx.get("positions"), make_cache=mk,
            cache_capacity=ctx.get("capacity", 0), mesh=ctx.get("mesh"))
    elif spec.mixer == "rglru":
        y, mcache = rec.rglru_full(
            cfg, p["mixer"], h,
            h0=cache["mixer"]["h"] if cache else None,
            conv0=cache["mixer"]["conv"] if cache else None, make_cache=mk)
    else:  # rwkv
        y, mcache = rec.rwkv_tmix_full(
            cfg, p["mixer"], h, cache=cache["mixer"] if cache else None,
            make_cache=mk)
    x = x + y.astype(x.dtype)

    ccache = None
    if spec.cross:
        hc = apply_norm(cfg, p["norm_cross"], x)
        ckv = attn.cross_kv(cfg, p["cross"], ctx["enc_out"])
        q = jnp.einsum("bsd,dhk->bshk", hc.astype(ckv["k"].dtype),
                       p["cross"]["wq"].astype(ckv["k"].dtype))
        if "bq" in p["cross"]:
            q = q + p["cross"]["bq"].astype(q.dtype)
        o = attn.attention_core(
            q, ckv["k"], ckv["v"], causal=False, window=0,
            q_pos=jnp.arange(q.shape[1]), k_pos=jnp.arange(ckv["k"].shape[1]))
        y = attn._out_proj(cfg, p["cross"], o)
        x = x + y.astype(x.dtype)
        ccache = ckv if mk else None

    h2 = apply_norm(cfg, p["norm2"], x)
    fcache = None
    if spec.ffn == "moe":
        y2, aux = moe_mod.apply_moe(cfg, p["ffn"], h2,
                                    mesh=ctx.get("mesh"))
    elif spec.ffn == "channelmix":
        y2, fcache = rec.channelmix_full(
            cfg, p["ffn"], h2, cache=cache["ffn"] if cache else None,
            make_cache=mk)
    else:
        y2 = apply_ffn(cfg, p["ffn"], h2, kind=spec.ffn)
    x = x + y2.astype(x.dtype)

    new_cache = None
    if mk:
        new_cache = {}
        if mcache is not None:
            new_cache["mixer"] = mcache
        if ccache is not None:
            new_cache["cross"] = ccache
        if fcache is not None:
            new_cache["ffn"] = fcache
    return x, new_cache, aux


def apply_layer_decode(cfg, spec, p, x, cache, ctx):
    """One-token layer step. Returns (x', cache')."""
    pos = ctx["pos"]
    h = apply_norm(cfg, p["norm1"], x)
    new_cache = dict(cache)

    # Paged serving: per-slot rows of *dead* slots (mid-prefill, parked;
    # position -1) must keep their state — a recurrent update driven by
    # the dead slot's placeholder token would corrupt the state its next
    # prefill chunk (or swap refault) reads back. Attention K/V pages
    # are immune: dead slots never have a write position.
    live = ctx.get("positions") if ctx.get("block_tables") is not None \
        else None

    def keep_rows(old, new):
        if live is None:
            return new
        m = live.reshape((-1,) + (1,) * (new.ndim - 1)) >= 0
        return jnp.where(m, new.astype(old.dtype), old)

    if spec.mixer in ("attn", "swa"):
        window = cfg.window if spec.mixer == "swa" else 0
        if ctx.get("block_tables") is not None:       # paged serving path
            y, new_cache["mixer"] = attn.attn_decode_paged(
                cfg, p["mixer"], h, cache["mixer"], ctx["positions"],
                ctx["block_tables"], window=window)
        else:
            y, new_cache["mixer"] = attn.attn_decode(
                cfg, p["mixer"], h, cache["mixer"], pos, window=window,
                mesh=ctx.get("mesh"))
    elif spec.mixer == "rglru":
        y, mc = rec.rglru_decode(cfg, p["mixer"], h, cache["mixer"])
        new_cache["mixer"] = jax.tree.map(keep_rows, cache["mixer"], mc)
    else:
        y, mc = rec.rwkv_tmix_decode(cfg, p["mixer"], h, cache["mixer"])
        new_cache["mixer"] = jax.tree.map(keep_rows, cache["mixer"], mc)
    x = x + y.astype(x.dtype)

    if spec.cross:
        hc = apply_norm(cfg, p["norm_cross"], x)
        y = attn.cross_attn_decode(cfg, p["cross"], hc, cache["cross"])
        x = x + y.astype(x.dtype)

    h2 = apply_norm(cfg, p["norm2"], x)
    if spec.ffn == "moe":
        y2, _ = moe_mod.apply_moe(cfg, p["ffn"], h2, mesh=ctx.get("mesh"))
    elif spec.ffn == "channelmix":
        y2, fc = rec.channelmix_decode(cfg, p["ffn"], h2, cache["ffn"])
        new_cache["ffn"] = jax.tree.map(keep_rows, cache["ffn"], fc)
    else:
        y2 = apply_ffn(cfg, p["ffn"], h2, kind=spec.ffn)
    return x + y2.astype(x.dtype), new_cache


def apply_layer_chunk(cfg, spec, p, x, cache, ctx):
    """One slot's prompt *chunk* through the paged state (chunked
    prefill). x (1, L, D); attn/swa leaves are shared page pools
    (written via the slot's ``block_row``), everything else lives in
    per-slot batch rows — the slot's row is sliced out as the initial
    state and the final state written back, so no other slot is
    touched. Returns (x', cache')."""
    slot = ctx["slot"]

    def row(leaf):
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=0)

    def put(old, new):
        return jax.lax.dynamic_update_slice_in_dim(old, new.astype(
            old.dtype), slot, axis=0)

    h = apply_norm(cfg, p["norm1"], x)
    new_cache = dict(cache)
    if spec.mixer in ("attn", "swa"):
        window = cfg.window if spec.mixer == "swa" else 0
        y, new_cache["mixer"] = attn.attn_prefill_chunk_paged(
            cfg, p["mixer"], h, cache["mixer"], ctx["positions"],
            ctx["block_row"], window=window)
    elif spec.mixer == "rglru":
        y, mc = rec.rglru_full(
            cfg, p["mixer"], h, h0=row(cache["mixer"]["h"]),
            conv0=row(cache["mixer"]["conv"]), make_cache=True)
        new_cache["mixer"] = {k: put(cache["mixer"][k], mc[k])
                              for k in cache["mixer"]}
    else:  # rwkv
        c0 = {k: row(v) for k, v in cache["mixer"].items()}
        y, mc = rec.rwkv_tmix_full(cfg, p["mixer"], h, cache=c0,
                                   make_cache=True)
        new_cache["mixer"] = {k: put(cache["mixer"][k], mc[k])
                              for k in cache["mixer"]}
    x = x + y.astype(x.dtype)

    if spec.cross:
        raise NotImplementedError(
            "chunked prefill: enc-dec cross attention (whisper prefills "
            "monolithically)")

    h2 = apply_norm(cfg, p["norm2"], x)
    if spec.ffn == "moe":
        y2, _ = moe_mod.apply_moe(cfg, p["ffn"], h2, mesh=ctx.get("mesh"))
    elif spec.ffn == "channelmix":
        c0 = {k: row(v) for k, v in cache["ffn"].items()}
        y2, fc = rec.channelmix_full(cfg, p["ffn"], h2, cache=c0,
                                     make_cache=True)
        new_cache["ffn"] = {k: put(cache["ffn"][k], fc[k])
                            for k in cache["ffn"]}
    else:
        y2 = apply_ffn(cfg, p["ffn"], h2, kind=spec.ffn)
    return x + y2.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Stack init / apply over the segment layout
# ---------------------------------------------------------------------------


def init_stack(cfg, key, specs):
    layout = build_layout(cfg, specs)
    segs = []
    for entry in layout:
        if entry[0] == "unroll":
            _, sp = entry
            key, *ks = jax.random.split(key, len(sp) + 1)
            segs.append([init_layer(cfg, k, s) for k, s in zip(ks, sp)])
        else:
            _, period, n = entry
            key, sub = jax.random.split(key)

            def one(k, period=period):
                kk = jax.random.split(k, len(period))
                return [init_layer(cfg, kk[i], s)
                        for i, s in enumerate(period)]

            segs.append(jax.vmap(one)(jax.random.split(sub, n)))
    return segs


def init_stack_cache(cfg, specs, batch, capacity, enc_len=0):
    layout = build_layout(cfg, specs)
    out = []
    for entry in layout:
        if entry[0] == "unroll":
            out.append([init_layer_cache(cfg, s, batch, capacity, enc_len)
                        for s in entry[1]])
        else:
            _, period, n = entry
            one = [init_layer_cache(cfg, s, batch, capacity, enc_len)
                   for s in period]
            out.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), one))
    return out


def init_paged_state(cfg, specs, batch, num_pages, page_size, enc_len=0):
    """Paged serving state: attn/swa → shared page pools, everything else
    per-slot rows. Structure mirrors ``init_stack_cache`` (scan segments
    stack on a leading periods axis)."""
    layout = build_layout(cfg, specs)
    out = []
    for entry in layout:
        if entry[0] == "unroll":
            out.append([init_layer_paged(cfg, s, batch, num_pages,
                                         page_size, enc_len)
                        for s in entry[1]])
        else:
            _, period, n = entry
            one = [init_layer_paged(cfg, s, batch, num_pages, page_size,
                                    enc_len)
                   for s in period]
            out.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), one))
    return out


def write_prefill_to_state(cfg, specs, state, new_caches, slot, block_row,
                           length, page_size):
    """Scatter one newcomer's batch=1 prefill caches into the paged
    state: K/V tokens ``t < length`` go to page ``block_row[t // ps]``
    offset ``t % ps`` of each layer's pool; per-slot leaves (recurrent
    state, cross-attn K/V, channelmix shifts) overwrite row ``slot``.
    ``slot`` and ``length`` are static (jit per distinct prompt length —
    the same compile granularity as prefill itself); no other slot's
    pages or rows are read or written. Returns the updated state."""
    layout = build_layout(cfg, specs)
    t = np.arange(length)
    pages = block_row[t // page_size]                 # (length,) traced
    offs = jnp.asarray(t % page_size)

    def write_pool(pool, new, scan):
        # pool (…, P, ps, Hkv, hd); new (…, 1, L, Hkv, hd) with L ≥ length
        if scan:
            return pool.at[:, pages, offs].set(new[:, 0, :length])
        return pool.at[pages, offs].set(new[0, :length])

    def write_row(old, new, scan):
        if scan:
            return old.at[:, slot].set(new[:, 0])
        return old.at[slot].set(new[0])

    def write_layer(spec, sc, nc, scan):
        out = {}
        for key, leaf in sc.items():
            if key == "mixer" and spec.mixer in ("attn", "swa"):
                out[key] = {kk: write_pool(leaf[kk], nc[key][kk], scan)
                            for kk in ("k", "v")}
            else:
                out[key] = jax.tree.map(
                    lambda o, n: write_row(o, n, scan), leaf, nc[key])
        return out

    new_state = []
    for si, entry in enumerate(layout):
        if entry[0] == "unroll":
            new_state.append([
                write_layer(spec, state[si][li], new_caches[si][li], False)
                for li, spec in enumerate(entry[1])])
        else:
            _, period, n = entry
            new_state.append([
                write_layer(spec, state[si][li], new_caches[si][li], True)
                for li, spec in enumerate(period)])
    return new_state


def _kv_pool_sites(cfg, specs):
    """Yield ``(si, li, scan)`` for every attn/swa layer whose paged
    state holds K/V page pools — the walk shared by the per-page
    copy/gather/scatter helpers below."""
    for si, entry in enumerate(build_layout(cfg, specs)):
        scan = entry[0] != "unroll"
        for li, spec in enumerate(entry[1]):
            if spec.mixer in ("attn", "swa"):
                yield si, li, scan


def _map_kv_pools(cfg, specs, state, fn):
    """Rebuild ``state`` with ``fn(pool, scan)`` applied to every K and
    V page pool (other leaves untouched)."""
    new_state = [list(seg) for seg in state]
    for si, li, scan in _kv_pool_sites(cfg, specs):
        layer = dict(new_state[si][li])
        mixer = dict(layer["mixer"])
        for kk in ("k", "v"):
            mixer[kk] = fn(mixer[kk], scan)
        layer["mixer"] = mixer
        new_state[si][li] = layer
    return new_state


def copy_kv_page_in_state(cfg, specs, state, src, dst):
    """Device-side page copy ``dst ← src`` across every layer's K/V
    pool — the copy-on-write data move (the MMU's ``fork_page`` swaps
    the mapping, this copies the bytes). Pools are (P, ps, Hkv, hd)
    unrolled, (n, P, ps, Hkv, hd) under scan."""
    def cp(pool, scan):
        if scan:
            return pool.at[:, dst].set(pool[:, src])
        return pool.at[dst].set(pool[src])
    return _map_kv_pools(cfg, specs, state, cp)


def gather_kv_page(cfg, specs, state, page):
    """Read one physical page out of every layer's K/V pool → flat leaf
    list (layer-major, k then v) — the swap tier's device→host read."""
    leaves = []
    for si, li, scan in _kv_pool_sites(cfg, specs):
        for kk in ("k", "v"):
            pool = state[si][li]["mixer"][kk]
            leaves.append(pool[:, page] if scan else pool[page])
    return leaves


def scatter_kv_page(cfg, specs, state, page, leaves):
    """Inverse of :func:`gather_kv_page`: write the flat leaf list back
    into physical page ``page`` of every pool — the refault path."""
    it = iter(leaves)

    def wr(pool, scan):
        leaf = next(it)
        if scan:
            return pool.at[:, page].set(leaf)
        return pool.at[page].set(leaf)
    return _map_kv_pools(cfg, specs, state, wr)


def _state_row_keys(spec):
    """Cache keys of ``spec`` whose paged-state leaves are per-slot rows
    (batch-indexed) rather than shared K/V page pools: recurrent mixer
    state (rg-lru h/conv, rwkv shift/s), cross-attn K/V, channelmix
    shifts. Order is fixed — the gather/scatter leaf lists depend on it."""
    keys = []
    if spec.mixer not in ("attn", "swa"):
        keys.append("mixer")
    if spec.cross:
        keys.append("cross")
    if spec.ffn == "channelmix":
        keys.append("ffn")
    return keys


def _state_row_sites(cfg, specs):
    """Yield ``(si, li, keys, scan)`` for every layer holding per-slot
    rows — the walk shared by the row gather/scatter/reset helpers."""
    for si, entry in enumerate(build_layout(cfg, specs)):
        scan = entry[0] != "unroll"
        for li, spec in enumerate(entry[1]):
            keys = _state_row_keys(spec)
            if keys:
                yield si, li, keys, scan


def _map_state_rows(cfg, specs, state, fn):
    """Rebuild ``state`` with ``fn(leaf, scan)`` applied to every
    per-slot row leaf (K/V page pools untouched)."""
    new_state = [list(seg) for seg in state]
    for si, li, keys, scan in _state_row_sites(cfg, specs):
        layer = dict(new_state[si][li])
        for key in keys:
            layer[key] = jax.tree.map(lambda a: fn(a, scan), layer[key])
        new_state[si][li] = layer
    return new_state


def gather_state_row(cfg, specs, state, slot):
    """Read slot ``slot``'s row out of every per-slot leaf → flat leaf
    list (layer-major, sorted-key order within a layer) — the recurrent
    paged-state swap tier's device→host read. Rows are (B, …) unrolled,
    (n, B, …) under scan; the gathered leaves drop the batch axis."""
    leaves = []
    for si, li, keys, scan in _state_row_sites(cfg, specs):
        for key in keys:
            for leaf in jax.tree.leaves(state[si][li][key]):
                leaves.append(leaf[:, slot] if scan else leaf[slot])
    return leaves


def scatter_state_row(cfg, specs, state, slot, leaves):
    """Inverse of :func:`gather_state_row`: write the flat leaf list
    back into slot ``slot``'s rows — the recurrent-state refault path."""
    it = iter(leaves)

    def wr(leaf, scan):
        row = next(it)
        if scan:
            return leaf.at[:, slot].set(row.astype(leaf.dtype))
        return leaf.at[slot].set(row.astype(leaf.dtype))
    return _map_state_rows(cfg, specs, state, wr)


def reset_state_row(cfg, specs, state, slot):
    """Zero slot ``slot``'s per-slot rows — a fresh request admitted
    into a recycled slot must not read the previous occupant's recurrent
    state (chunked prefill reads rows as its initial state, so without
    this a recycled slot leaks state across requests)."""
    def zero(leaf, scan):
        if scan:
            return leaf.at[:, slot].set(0)
        return leaf.at[slot].set(0)
    return _map_state_rows(cfg, specs, state, zero)


def _maybe_remat(cfg, fn):
    remat = cfg.sharding.remat
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    # 'dots': keep projection outputs (cheap recompute, high memory)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)


def apply_stack_full(cfg, specs, segs, x, ctx, caches=None):
    """Full-sequence stack. Returns (x, new_caches, aux_sum)."""
    layout = build_layout(cfg, specs)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for si, entry in enumerate(layout):
        seg_params = segs[si]
        seg_cache = caches[si] if caches is not None else None
        if entry[0] == "unroll":
            sp = entry[1]
            ncs = []
            for li, spec in enumerate(sp):
                x, nc, aux = apply_layer_full(
                    cfg, spec, seg_params[li], x, ctx,
                    cache=seg_cache[li] if seg_cache else None)
                ncs.append(nc)
                aux_total = aux_total + aux
            new_caches.append(ncs)
        else:
            _, period, n = entry

            def body(carry, xs, period=period):
                xx, aux_acc = carry
                p_i = xs[0] if isinstance(xs, tuple) else xs
                c_i = xs[1] if isinstance(xs, tuple) else None
                ncs = []
                for li, spec in enumerate(period):
                    xx, nc, aux = apply_layer_full(
                        cfg, spec, p_i[li], xx, ctx,
                        cache=c_i[li] if c_i is not None else None)
                    ncs.append(nc)
                    aux_acc = aux_acc + aux
                return (xx, aux_acc), (ncs if ctx["make_cache"] else 0)

            body = _maybe_remat(cfg, body)
            xs = (seg_params, seg_cache) if seg_cache is not None \
                else seg_params
            (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), xs)
            new_caches.append(ys if ctx["make_cache"] else None)
    return x, (new_caches if ctx["make_cache"] else None), aux_total


def apply_stack_decode(cfg, specs, segs, x, caches, ctx):
    """One-token stack step. Returns (x, new_caches)."""
    layout = build_layout(cfg, specs)
    new_caches = []
    for si, entry in enumerate(layout):
        seg_params = segs[si]
        seg_cache = caches[si]
        if entry[0] == "unroll":
            ncs = []
            for li, spec in enumerate(entry[1]):
                x, nc = apply_layer_decode(
                    cfg, spec, seg_params[li], x, seg_cache[li], ctx)
                ncs.append(nc)
            new_caches.append(ncs)
        else:
            _, period, n = entry

            def body(xx, xs, period=period):
                p_i, c_i = xs
                ncs = []
                for li, spec in enumerate(period):
                    xx, nc = apply_layer_decode(
                        cfg, spec, p_i[li], xx, c_i[li], ctx)
                    ncs.append(nc)
                return xx, ncs

            x, ys = jax.lax.scan(body, x, (seg_params, seg_cache))
            new_caches.append(ys)
    return x, new_caches


def apply_stack_chunk(cfg, specs, segs, x, state, ctx):
    """One slot's prompt chunk through the paged state. Returns
    (x, state'). Mirrors ``apply_stack_decode``'s segment walk."""
    layout = build_layout(cfg, specs)
    new_state = []
    for si, entry in enumerate(layout):
        seg_params = segs[si]
        seg_state = state[si]
        if entry[0] == "unroll":
            ncs = []
            for li, spec in enumerate(entry[1]):
                x, nc = apply_layer_chunk(
                    cfg, spec, seg_params[li], x, seg_state[li], ctx)
                ncs.append(nc)
            new_state.append(ncs)
        else:
            _, period, n = entry

            def body(xx, xs, period=period):
                p_i, c_i = xs
                ncs = []
                for li, spec in enumerate(period):
                    xx, nc = apply_layer_chunk(
                        cfg, spec, p_i[li], xx, c_i[li], ctx)
                    ncs.append(nc)
                return xx, ncs

            x, ys = jax.lax.scan(body, x, (seg_params, seg_state))
            new_state.append(ys)
    return x, new_state
