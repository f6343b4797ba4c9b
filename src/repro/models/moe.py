"""Mixture-of-experts FFN: top-k routing with sort-based gather dispatch.

Why not GShard one-hot dispatch: the (tokens, E, capacity) dispatch tensor is
infeasible at 384 experts (kimi-k2). Instead we sort token→expert
assignments, place each assignment into a per-expert capacity buffer
(gather), run batched expert GEMMs (E, C, d) × (E, d, d_e), and scatter-add
the weighted results back — the MegaBlocks/MaxText-style dropping dispatch,
expressible in pure XLA ops (sort/gather/scatter) that GSPMD partitions
along the expert axis.

Differentiable end-to-end: gradients flow through gather/scatter and the
top-k *weights* (indices are integers and need no gradient).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, dt


def init_moe(cfg, key, n_experts=None, d_expert=None):
    m = cfg.moe
    E = n_experts or m.n_experts
    de = d_expert or m.d_expert
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, E, "float32"),  # fp32 router (std)
        "w_gate": _stacked(ks[1], E, d, de, cfg),
        "w_up": _stacked(ks[2], E, d, de, cfg),
        "w_down": _stacked(ks[3], E, de, d, cfg),
    }
    if m.n_shared_experts:
        dsh = de * m.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, d, dsh, cfg.param_dtype),
            "w_up": dense_init(k2, d, dsh, cfg.param_dtype),
            "w_down": dense_init(k3, dsh, d, cfg.param_dtype),
        }
    return p


def _stacked(key, E, d_in, d_out, cfg):
    return dense_init(key, E * d_in, d_out, cfg.param_dtype).reshape(
        E, d_in, d_out)


def capacity(n_tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(n_tokens * top_k * cf / n_experts) + 1
    return max(c, 1)


def apply_moe(cfg, p, x, mesh=None):
    """x: (B, S, d) → (y, aux_loss). Dispatches on cfg.sharding.moe_impl."""
    if (cfg.sharding.moe_impl == "ep" and mesh is not None
            and "model" in mesh.axis_names and mesh.shape["model"] > 1):
        return apply_moe_ep(cfg, p, x, mesh)
    return apply_moe_gather(cfg, p, x)


def apply_moe_gather(cfg, p, x):
    """Baseline: pjit auto-spmd sort/gather capacity dispatch."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.n_experts, m.top_k
    C = capacity(T, K, E, m.capacity_factor)
    cd = dt(cfg.compute_dtype)
    xf = x.reshape(T, d)

    # --- routing (fp32) ---------------------------------------------------
    logits = jnp.dot(xf.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    top_w, top_i = jax.lax.top_k(probs, K)                     # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # --- load-balance auxiliary loss (GShard/Switch) ------------------------
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(top_i, E, dtype=jnp.float32).sum(1), axis=0)  # (E,)
    prob_frac = probs.mean(axis=0)
    aux = E * jnp.sum(dispatch_frac / K * prob_frac) * m.router_aux_coef

    # --- sort-based dispatch -------------------------------------------------
    eid = top_i.reshape(-1)                                    # (T·K,) token-major
    tok = jnp.arange(T * K, dtype=jnp.int32) // K
    w = top_w.reshape(-1)
    order = jnp.argsort(eid)                                   # stable
    seid, stok, sw = eid[order], tok[order], w[order]
    counts = jnp.bincount(eid, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[seid]
    keep = pos < C
    slot = seid * C + jnp.minimum(pos, C - 1)                  # (T·K,)

    slot_tok = jnp.full((E * C,), T, dtype=jnp.int32)
    slot_tok = slot_tok.at[jnp.where(keep, slot, E * C)].set(
        stok, mode="drop")
    x_pad = jnp.concatenate(
        [xf.astype(cd), jnp.zeros((1, d), cd)], axis=0)
    xe = x_pad[slot_tok].reshape(E, C, d)                      # gather

    # --- expert computation (batched GEMMs) ----------------------------------
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(cd))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(cd))

    # --- combine (scatter-add weighted contributions) ------------------------
    contrib = ye.reshape(E * C, d)[slot]                       # (T·K, d)
    contrib = contrib * (sw * keep).astype(cd)[:, None]
    y = jnp.zeros((T, d), cd).at[stok].add(contrib)

    if "shared" in p:
        sh = p["shared"]
        gs = jnp.dot(xf.astype(cd), sh["w_gate"].astype(cd))
        us = jnp.dot(xf.astype(cd), sh["w_up"].astype(cd))
        y = y + jnp.dot(jax.nn.silu(gs) * us, sh["w_down"].astype(cd))

    return y.reshape(B, S, d), aux


# ===========================================================================
# Expert-parallel shard_map path (beyond-paper optimized, §Perf)
# ===========================================================================
#
# Measured failure of the gather baseline under GSPMD: expert GEMMs and
# token buffers get replicated across the mesh (mixtral train_4k:
# useful_ratio 0.003, 1.5 TB/device). The EP path makes the communication
# pattern explicit:
#
#   tokens (replicated over "model" within a data row) are SPLIT over the
#   model axis → each model shard routes its token slice → all_to_all
#   sends each expert's tokens to the shard owning it (E/n_model experts
#   per shard) → local batched GEMMs → all_to_all back → local combine →
#   all_gather reassembles the token slices.
#
# Per-layer comm per device ≈ 3 × (T_loc/n_model)·K·d·2B (two all_to_alls
# + one all-gather) instead of replicated expert weights + global sorts.


def _route_dispatch_local(cfg, xf, router, E, C):
    """Local top-k routing + capacity dispatch. xf: (T, d) fp32-routable.

    Returns (xe (E, C, d), slot, stok, sw·keep, aux)."""
    m = cfg.moe
    T, d = xf.shape
    K = m.top_k
    cd = xf.dtype
    logits = jnp.dot(xf.astype(jnp.float32),
                     router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(top_i, E, dtype=jnp.float32).sum(1), axis=0)
    aux = E * jnp.sum(dispatch_frac / K * probs.mean(0)) * m.router_aux_coef

    eid = top_i.reshape(-1)
    tok = jnp.arange(T * K, dtype=jnp.int32) // K
    w = top_w.reshape(-1)
    order = jnp.argsort(eid)
    seid, stok, sw = eid[order], tok[order], w[order]
    counts = jnp.bincount(eid, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[seid]
    keep = pos < C
    slot = seid * C + jnp.minimum(pos, C - 1)
    slot_tok = jnp.full((E * C,), T, dtype=jnp.int32)
    slot_tok = slot_tok.at[jnp.where(keep, slot, E * C)].set(
        stok, mode="drop")
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), cd)], axis=0)
    xe = x_pad[slot_tok].reshape(E, C, d)
    return xe, slot, stok, (sw * keep).astype(cd), aux


def apply_moe_ep(cfg, p, x, mesh):
    """shard_map expert parallelism over the "model" axis.

    Two regimes:
    * many small experts (E % n_model == 0, e.g. kimi 384/16): token-routing
      EP — all_to_all sends each expert's tokens to its owner shard;
    * few big experts (E < n_model, e.g. mixtral 8 on 16): expert-TP —
      every shard holds a d_e slice of EVERY expert; tokens stay put and
      partial outputs are psum-combined (Megatron-style FFN TP).
    """
    import numpy as np
    n_model = int(mesh.shape["model"])
    if cfg.moe.n_experts % n_model != 0:
        return _apply_moe_expert_tp(cfg, p, x, mesh)
    B, S, _ = x.shape
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if (B * S <= 2048 and cfg.sharding.shard_experts_data
            and cfg.moe.d_expert % n_dp == 0):
        # decode regime: tokens are tiny — keep weights 2-D sharded
        # (E × model, d_e × data → 1T params FIT 256 chips at rest) and
        # replicate the few tokens instead (all-gather + psum are ~MBs)
        return _apply_moe_inference_2d(cfg, p, x, mesh)
    return _apply_moe_token_routing(cfg, p, x, mesh)


def _apply_moe_inference_2d(cfg, p, x, mesh):
    from jax.sharding import PartitionSpec as P
    import numpy as np

    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_experts, m.top_k
    n_model = int(mesh.shape["model"])
    E_loc = E // n_model
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    b_ok = B % dp_size == 0
    cd = dt(cfg.compute_dtype)

    def inner(xl, router, wg, wu, wd, shared):
        # xl (B_loc, S, d); wg/wu (E_loc, d, de_loc); wd (E_loc, de_loc, d)
        xg = xl
        if b_ok and dp:
            for a in reversed(dp):
                xg = jax.lax.all_gather(xg, a, axis=0, tiled=True)
        Bg = xg.shape[0]
        T = Bg * S
        xf = xg.reshape(T, d).astype(cd)
        C = capacity(T, K, E, m.capacity_factor)
        xe, slot, stok, sw, aux = _route_dispatch_local(
            cfg, xf, router, E, C)
        midx = jax.lax.axis_index("model")
        xe_loc = jax.lax.dynamic_slice_in_dim(xe, midx * E_loc, E_loc, 0)
        g = jnp.einsum("ecd,edf->ecf", xe_loc, wg.astype(cd))
        u = jnp.einsum("ecd,edf->ecf", xe_loc, wu.astype(cd))
        h = jax.nn.silu(g) * u
        ye_loc = jnp.einsum("ecf,efd->ecd", h, wd.astype(cd))
        ye = jnp.zeros((E, C, d), cd)
        ye = jax.lax.dynamic_update_slice_in_dim(ye, ye_loc, midx * E_loc, 0)
        contrib = ye.reshape(E * C, d)[slot] * sw[:, None]
        y = jnp.zeros((T, d), cd).at[stok].add(contrib)
        y = jax.lax.psum(y, "model")           # sum expert shards
        for a in dp:
            y = jax.lax.psum(y, a)             # sum d_e slices
        if shared is not None:
            gs = jnp.dot(xf, shared["w_gate"].astype(cd))
            us = jnp.dot(xf, shared["w_up"].astype(cd))
            y = y + jnp.dot(jax.nn.silu(gs) * us,
                            shared["w_down"].astype(cd))
        yb = y.reshape(Bg, S, d)
        if b_ok and dp:
            # take back my batch rows (token order is dp-major from the
            # tiled all_gather)
            Bl = Bg // dp_size
            didx = jax.lax.axis_index(dp[0])
            for a in dp[1:]:
                didx = didx * mesh.shape[a] + jax.lax.axis_index(a)
            yb = jax.lax.dynamic_slice_in_dim(yb, didx * Bl, Bl, 0)
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        aux = jax.lax.pmean(aux, "model")
        return yb, aux

    bspec = dp if (dp and b_ok) else None
    xspec = P(bspec, None, None)
    ed = dp[-1] if dp else None                # d_e sharded over "data"
    shared_spec = (jax.tree.map(lambda _: P(None, None), p["shared"])
                   if "shared" in p else None)
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(xspec, P(None, None), P("model", None, ed),
                  P("model", None, ed), P("model", ed, None), shared_spec),
        out_specs=(xspec, P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
              p.get("shared"))


def _apply_moe_expert_tp(cfg, p, x, mesh):
    from jax.sharding import PartitionSpec as P
    import numpy as np

    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_experts, m.top_k
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    cd = dt(cfg.compute_dtype)

    def inner(xl, router, wg, wu, wd, shared):
        # xl (B_loc, S, d); w gate/up (E, d, de_loc); w down (E, de_loc, d)
        Bl = xl.shape[0]
        T = Bl * S
        xf = xl.reshape(T, d).astype(cd)
        C = capacity(T, K, E, m.capacity_factor)
        xe, slot, stok, sw, aux = _route_dispatch_local(
            cfg, xf, router, E, C)
        g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(cd))
        u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(cd))
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(cd))   # partial over de
        contrib = ye.reshape(E * C, d)[slot] * sw[:, None]
        y_part = jnp.zeros((T, d), cd).at[stok].add(contrib)
        y = jax.lax.psum(y_part, "model")                   # combine slices
        if shared is not None:
            gs = jnp.dot(xf, shared["w_gate"].astype(cd))
            us = jnp.dot(xf, shared["w_up"].astype(cd))
            y = y + jnp.dot(jax.nn.silu(gs) * us,
                            shared["w_down"].astype(cd))
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        return y.reshape(Bl, S, d), aux

    bspec = dp if (dp and B % dp_size == 0) else None
    xspec = P(bspec, None, None)
    shared_spec = (jax.tree.map(lambda _: P(None, None), p["shared"])
                   if "shared" in p else None)
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(xspec, P(None, None), P(None, None, "model"),
                  P(None, None, "model"), P(None, "model", None),
                  shared_spec),
        out_specs=(xspec, P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
              p.get("shared"))


def _apply_moe_token_routing(cfg, p, x, mesh):
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_experts, m.top_k
    n_model = int(mesh.shape["model"])
    E_loc = E // n_model
    assert E % n_model == 0, (E, n_model)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    cd = dt(cfg.compute_dtype)

    def inner(xl, router, wg, wu, wd, shared):
        # xl (B_loc, S, d) replicated over model; w* (E_loc, d, de)
        Bl = xl.shape[0]
        T = Bl * S
        xf = xl.reshape(T, d).astype(cd)
        midx = jax.lax.axis_index("model")
        T_m = -(-T // n_model)                    # padded slice per shard
        pad = T_m * n_model - T
        xf_p = jnp.pad(xf, ((0, pad), (0, 0)))
        x_m = jax.lax.dynamic_slice_in_dim(xf_p, midx * T_m, T_m, axis=0)

        C = capacity(T_m, K, E, m.capacity_factor)
        xe, slot, stok, sw, aux = _route_dispatch_local(
            cfg, x_m, router, E, C)

        # token routing: (E, C, d) → peers; receive my experts' tokens
        send = xe.reshape(n_model, E_loc, C, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        xe_loc = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_model * C, d)

        g = jnp.einsum("ecd,edf->ecf", xe_loc, wg.astype(cd))
        u = jnp.einsum("ecd,edf->ecf", xe_loc, wu.astype(cd))
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(cd))

        back = ye.reshape(E_loc, n_model, C, d).transpose(1, 0, 2, 3)
        got = jax.lax.all_to_all(back, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        ye_full = got.reshape(E * C, d)           # my tokens' expert outputs

        contrib = ye_full[slot] * sw[:, None]
        y_m = jnp.zeros((T_m, d), cd).at[stok].add(contrib)

        if shared is not None:
            # shared expert on the LOCAL token slice (sharded compute —
            # computing it on all T tokens per shard measurably dominated
            # the EP compute term on kimi; §Perf iteration 2)
            gs = jnp.dot(x_m, shared["w_gate"].astype(cd))
            us = jnp.dot(x_m, shared["w_up"].astype(cd))
            y_m = y_m + jnp.dot(jax.nn.silu(gs) * us,
                                shared["w_down"].astype(cd))

        # reassemble token slices across the model axis
        y_all = jax.lax.all_gather(y_m, "model", axis=0, tiled=True)
        y = y_all[:T].reshape(Bl, S, d)

        aux = jax.lax.pmean(aux, "model")
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        return y, aux

    import numpy as np
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    bspec = dp if (dp and B % dp_size == 0) else None
    xspec = P(bspec, None, None)
    wspec = P("model", None, None)
    shared_spec = (jax.tree.map(lambda _: P(None, None), p["shared"])
                   if "shared" in p else None)
    shared_arg = p.get("shared")

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(xspec, P(None, None), wspec, wspec, wspec, shared_spec),
        out_specs=(xspec, P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
              shared_arg)
