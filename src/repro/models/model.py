"""Public model API: one ``Model`` facade per architecture config.

Families and their batch dicts
------------------------------
dense/moe/ssm/hybrid : {"tokens" (B,S), "labels" (B,S), "mask" (B,S)}
vlm                  : + {"patches" (B, n_img, d_in)} — ViT frontend STUB;
                       tokens cover S - n_img text positions
audio (whisper)      : {"frames" (B, enc_len, d_in)} — conv-stem STUB;
                       tokens/labels are decoder side

All entry points are pure functions usable under jit/pjit and AOT
(``jax.eval_shape`` for the dry-run).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import use_pallas
from repro.models import lm
from repro.models.attention import cross_kv
from repro.models.layers import (abs_position_vector, add_abs_positions,
                                 apply_norm, dense_init, dt, embed_init,
                                 init_norm, softmax_cross_entropy)


class Model:
    """Facade bundling init/apply for one architecture."""

    def __init__(self, cfg, mesh=None):
        self.cfg = cfg
        self.mesh = mesh      # enables shard_map paths (EP MoE, split-KV)
        self.specs = lm.layer_specs(cfg, cross=cfg.is_encdec)
        self.enc_specs = None
        if cfg.is_encdec:
            enc_cfg = cfg
            assert (cfg.encoder.d_model or cfg.d_model) == cfg.d_model, \
                "encoder d_model must match decoder (whisper-medium does)"
            self.enc_specs = tuple(
                lm.LayerSpec("attn", "gelu", cfg.d_ff, False)
                for _ in range(cfg.encoder.n_layers))

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        params = {
            "tok_embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                    cfg.param_dtype),
            "segments": lm.init_stack(cfg, ks[1], self.specs),
            "final_norm": init_norm(cfg),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[2], cfg.d_model,
                                           cfg.padded_vocab,
                                           cfg.param_dtype, scale=0.02)
        if cfg.frontend is not None and cfg.frontend.kind == "vision":
            params["projector"] = {
                "w1": dense_init(ks[3], cfg.frontend.d_in, cfg.d_model,
                                 cfg.param_dtype),
                "w2": dense_init(ks[4], cfg.d_model, cfg.d_model,
                                 cfg.param_dtype),
            }
        if cfg.is_encdec:
            params["encoder"] = {
                "segments": lm.init_stack(cfg, ks[5], self.enc_specs),
                "final_norm": init_norm(cfg),
            }
            if cfg.frontend.d_in != cfg.d_model:
                params["enc_proj"] = dense_init(
                    ks[6], cfg.frontend.d_in, cfg.d_model, cfg.param_dtype)
        return params

    # ------------------------------------------------------------------
    # Embedding assembly
    # ------------------------------------------------------------------
    def _embed_tokens(self, params, tokens):
        cd = dt(self.cfg.compute_dtype)
        return params["tok_embed"].astype(cd)[tokens]

    def _project_patches(self, params, patches):
        cd = dt(self.cfg.compute_dtype)
        pr = params["projector"]
        h = jax.nn.gelu(jnp.dot(patches.astype(cd), pr["w1"].astype(cd)))
        return jnp.dot(h, pr["w2"].astype(cd))

    def _lm_logits(self, params, x):
        cfg = self.cfg
        cd = dt(cfg.compute_dtype)
        x = apply_norm(cfg, params["final_norm"], x)
        head = (params["tok_embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.dot(x.astype(cd), head.astype(cd))
        if cfg.padded_vocab != cfg.vocab:   # mask padded vocab columns
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
            logits = logits + jnp.where(pad_mask, -1e30, 0.0).astype(
                logits.dtype)
        return logits

    # ------------------------------------------------------------------
    # Encoder (whisper)
    # ------------------------------------------------------------------
    def encode(self, params, frames):
        cfg = self.cfg
        cd = dt(cfg.compute_dtype)
        x = frames.astype(cd)
        if "enc_proj" in params:
            x = jnp.dot(x, params["enc_proj"].astype(cd))
        x = add_abs_positions(x)
        ctx = {"mode": "full", "causal": False, "make_cache": False,
               "positions": jnp.arange(x.shape[1])}
        x, _, _ = lm.apply_stack_full(cfg, self.enc_specs,
                                      params["encoder"]["segments"], x, ctx)
        return apply_norm(cfg, params["encoder"]["final_norm"], x)

    # ------------------------------------------------------------------
    # Full-sequence forward (train path)
    # ------------------------------------------------------------------
    def forward(self, params, batch):
        """→ (logits (B,S,V), aux_loss)."""
        cfg = self.cfg
        x, enc_out = self._assemble_inputs(params, batch)
        ctx = {"mode": "full", "causal": True, "make_cache": False,
               "positions": jnp.arange(x.shape[1]), "mesh": self.mesh}
        if enc_out is not None:
            ctx["enc_out"] = enc_out
        x, _, aux = lm.apply_stack_full(cfg, self.specs, params["segments"],
                                        x, ctx)
        return self._lm_logits(params, x), aux

    def _assemble_inputs(self, params, batch):
        cfg = self.cfg
        x = self._embed_tokens(params, batch["tokens"])
        enc_out = None
        if cfg.family == "vlm":
            pre = self._project_patches(params, batch["patches"])
            x = jnp.concatenate([pre, x], axis=1)
        if cfg.is_encdec:
            enc_out = self.encode(params, batch["frames"])
        if not cfg.use_rope:
            x = add_abs_positions(x)
        return x, enc_out

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        ce, n_tok = softmax_cross_entropy(
            logits, batch["labels"], batch.get("mask"))
        return ce + aux, {"ce": ce, "aux": aux, "n_tok": n_tok}

    # ------------------------------------------------------------------
    # Prefill → (last-token logits, caches)
    # ------------------------------------------------------------------
    def prefill(self, params, batch, capacity=None):
        cfg = self.cfg
        x, enc_out = self._assemble_inputs(params, batch)
        S = x.shape[1]
        ctx = {"mode": "full", "causal": True, "make_cache": True,
               "capacity": capacity or S, "positions": jnp.arange(S),
               "mesh": self.mesh}
        if enc_out is not None:
            ctx["enc_out"] = enc_out
        x, caches, _ = lm.apply_stack_full(cfg, self.specs,
                                           params["segments"], x, ctx)
        logits = self._lm_logits(params, x[:, -1:])[:, 0]
        return logits, caches

    # ------------------------------------------------------------------
    # Decode: one token against caches
    # ------------------------------------------------------------------
    def decode(self, params, caches, token, pos):
        """token (B,1) int32; pos scalar int32 → (logits (B,V), caches')."""
        cfg = self.cfg
        x = self._embed_tokens(params, token)
        if not cfg.use_rope:
            x = x + abs_position_vector(pos, cfg.d_model).astype(x.dtype)
        ctx = {"mode": "decode", "pos": pos, "mesh": self.mesh}
        x, caches = lm.apply_stack_decode(cfg, self.specs,
                                          params["segments"], x, caches, ctx)
        return self._lm_logits(params, x[:, -1:])[:, 0], caches

    def init_cache(self, batch_size, capacity):
        enc_len = self.cfg.encoder.seq_len if self.cfg.is_encdec else 0
        return lm.init_stack_cache(self.cfg, self.specs, batch_size,
                                   capacity, enc_len=enc_len)

    # ------------------------------------------------------------------
    # Paged serving path (MMU-backed KV pages; see serving/paged_kv.py)
    # ------------------------------------------------------------------
    def init_paged_state(self, batch_size, num_pages, page_size,
                         enc_len=None):
        """Serving state whose attn/swa leaves are shared page pools
        (num_pages, page_size, Hkv, hd); per-slot rows elsewhere."""
        if enc_len is None:
            enc_len = self.cfg.encoder.seq_len if self.cfg.is_encdec else 0
        return lm.init_paged_state(self.cfg, self.specs, batch_size,
                                   num_pages, page_size, enc_len=enc_len)

    def write_prefill_paged(self, state, caches, slot, block_row, length,
                            page_size):
        """Scatter a batch=1 prefill cache into slot ``slot``'s leased
        pages/rows — O(newcomer), no other slot touched."""
        return lm.write_prefill_to_state(self.cfg, self.specs, state,
                                         caches, slot, block_row, length,
                                         page_size)

    def decode_paged(self, params, state, token, positions, block_tables):
        """token (B,1) int32; positions (B,) int32 per-slot write
        positions (-1 = dead slot); block_tables (B, nb) int32 →
        (logits (B,V), state')."""
        cfg = self.cfg
        x = self._embed_tokens(params, token)
        if not cfg.use_rope:
            pvec = jnp.clip(positions, 0, None)
            x = x + abs_position_vector(pvec, cfg.d_model)[:, None, :] \
                .astype(x.dtype)
        ctx = {"mode": "decode", "pos": positions, "positions": positions,
               "block_tables": block_tables, "mesh": self.mesh}
        x, state = lm.apply_stack_decode(cfg, self.specs,
                                         params["segments"], x, state, ctx)
        return self._lm_logits(params, x[:, -1:])[:, 0], state

    def prefill_chunk_paged(self, params, state, tokens, slot, block_row,
                            start):
        """Chunked prefill: one slot's prompt chunk against the paged
        state (the engine interleaves these with decode steps so a
        newcomer never stalls the batch).

        tokens (1, L) int32 chunk of the prompt; slot () int32 batch
        row; block_row (nb,) int32 the slot's block table; start ()
        int32 absolute position of ``tokens[0]``. → (logits (1, V) of
        the chunk's last token, state'). jit specializes on L — the
        engine quantizes chunk lengths so the compile universe stays
        small."""
        cfg = self.cfg
        if cfg.family == "vlm" or cfg.is_encdec:
            raise NotImplementedError(
                "chunked prefill: vlm/enc-dec frontends prefill "
                "monolithically")
        x = self._embed_tokens(params, tokens)
        positions = start + jnp.arange(tokens.shape[1])
        if not cfg.use_rope:
            x = x + abs_position_vector(positions, cfg.d_model)[None] \
                .astype(x.dtype)
        ctx = {"mode": "chunk", "positions": positions, "slot": slot,
               "block_row": block_row, "mesh": self.mesh}
        x, state = lm.apply_stack_chunk(cfg, self.specs,
                                        params["segments"], x, state, ctx)
        return self._lm_logits(params, x[:, -1:])[:, 0], state

    def decode_paged_fused(self, params, state, token, positions,
                           block_tables, temps, step):
        """Fused decode step: paged attention (Pallas path keeps the new
        token's K/V in-register) + on-device argmax/Gumbel sampling —
        only (B,) token ids leave the device, not (B, V) logits.

        temps (B,) fp32 per-slot temperatures (0 = greedy); step ()
        int32 folds into the sampling key. → (tokens (B,) int32,
        state')."""
        cfg = self.cfg
        x = self._embed_tokens(params, token)
        if not cfg.use_rope:
            pvec = jnp.clip(positions, 0, None)
            x = x + abs_position_vector(pvec, cfg.d_model)[:, None, :] \
                .astype(x.dtype)
        ctx = {"mode": "decode", "pos": positions, "positions": positions,
               "block_tables": block_tables, "mesh": self.mesh}
        x, state = lm.apply_stack_decode(cfg, self.specs,
                                         params["segments"], x, state, ctx)
        logits = self._lm_logits(params, x[:, -1:])[:, 0]
        key = jax.random.fold_in(jax.random.PRNGKey(0x5e), step)
        noise = jax.random.gumbel(key, logits.shape, jnp.float32)
        if use_pallas():
            from repro.kernels.decode_attention.ops import sample_tokens_op
            toks = sample_tokens_op(logits, temps, noise)
        else:
            from repro.kernels.decode_attention.ops import sample_tokens_xla
            toks = sample_tokens_xla(logits, temps, noise)
        return toks, state

    def copy_kv_page(self, state, src, dst):
        """Device-side page copy ``dst ← src`` across every K/V pool —
        the copy-on-write byte move paired with ``SegmentPool.fork_page``
        (which swaps the mapping). src/dst are traced page indices."""
        return lm.copy_kv_page_in_state(self.cfg, self.specs, state,
                                        src, dst)

    def read_kv_page(self, state, page):
        """One physical page out of every K/V pool → flat leaf list
        (the swap tier's device→host read)."""
        return lm.gather_kv_page(self.cfg, self.specs, state, page)

    def write_kv_page(self, state, page, leaves):
        """Write a :meth:`read_kv_page` leaf list back into physical
        page ``page`` (the swap tier's refault write)."""
        return lm.scatter_kv_page(self.cfg, self.specs, state, page,
                                  leaves)

    def kv_page_bytes(self, page_size) -> int:
        """HBM bytes one KV page spans across all attn/swa layers — the
        MMU lease granularity for the paged cache."""
        cfg = self.cfg
        itemsize = jnp.dtype(dt(cfg.compute_dtype)).itemsize
        n_attn = sum(1 for s in self.specs if s.mixer in ("attn", "swa"))
        per_layer = 2 * page_size * cfg.n_kv_heads * cfg.d_head * itemsize
        return max(1, n_attn) * per_layer

    # ------------------------------------------------------------------
    # Paged recurrent state (per-slot rows; see serving/paged_state.py)
    # ------------------------------------------------------------------
    def read_state_row(self, state, slot):
        """One slot's per-slot rows (recurrent mixer state, cross-attn
        K/V, channelmix shifts) → flat leaf list (the recurrent-state
        swap tier's device→host read)."""
        return lm.gather_state_row(self.cfg, self.specs, state, slot)

    def write_state_row(self, state, slot, leaves):
        """Write a :meth:`read_state_row` leaf list back into slot
        ``slot``'s rows (the recurrent-state refault write)."""
        return lm.scatter_state_row(self.cfg, self.specs, state, slot,
                                    leaves)

    def reset_state_row(self, state, slot):
        """Zero slot ``slot``'s rows — admission into a recycled slot
        must not read the previous occupant's recurrent state."""
        return lm.reset_state_row(self.cfg, self.specs, state, slot)

    def state_row_bytes(self) -> int:
        """HBM bytes one slot's per-slot rows span across all layers —
        the MMU lease granularity for paged recurrent state. 0 for
        pure-attention stacks (their serving state is all KV pages)."""
        enc_len = self.cfg.encoder.seq_len if self.cfg.is_encdec else 0

        def probe():
            st = lm.init_paged_state(self.cfg, self.specs, 1, 1, 1,
                                     enc_len=enc_len)
            return lm.gather_state_row(self.cfg, self.specs, st, 0)
        leaves = jax.eval_shape(probe)
        return sum(int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
                   for leaf in leaves)

    # ------------------------------------------------------------------
    # Input specs (ShapeDtypeStruct stand-ins for the dry-run)
    # ------------------------------------------------------------------
    def input_specs(self, cell):
        """→ batch dict of ShapeDtypeStruct for the given ShapeCell."""
        cfg = self.cfg
        B, S = cell.global_batch, cell.seq_len
        f32 = jnp.float32
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if cell.kind == "decode":
            return {"token": sds((B, 1), i32)}
        batch = {}
        s_text = S
        if cfg.family == "vlm":
            s_text = S - cfg.frontend.n_tokens
            batch["patches"] = sds((B, cfg.frontend.n_tokens,
                                    cfg.frontend.d_in), f32)
        if cfg.is_encdec:
            batch["frames"] = sds((B, cfg.frontend.n_tokens,
                                   cfg.frontend.d_in), f32)
        batch["tokens"] = sds((B, s_text), i32)
        if cell.kind == "train":
            batch["labels"] = sds((B, S), i32)
            batch["mask"] = sds((B, S), f32)
        return batch


def build_model(cfg, mesh=None) -> Model:
    return Model(cfg, mesh=mesh)
