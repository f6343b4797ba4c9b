"""Continuous-batching serving engine over MMU-backed paged KV memory.

Each batch slot owns a *position* and a *block table* instead of the
whole batch sharing one scalar decode position:

* K/V live in shared physical page pools leased per-request from the
  software MMU (:class:`repro.serving.paged_kv.PagedKVCache`);
* admission prefills **only the newcomer** (batch=1, its own length) and
  scatters the result into freshly leased pages — O(newcomer), zero
  recompute on occupied slots, no left-padding to a shared position and
  no full re-prefill fallback (``stats.full_prefills`` stays 0);
* decode passes a per-slot ``(B,)`` positions vector (-1 marks a dead
  slot) plus the block tables; EOS recycling frees the slot's pages back
  to the MMU the moment it finishes.

The engine takes a ``Model`` and jits its prefill / paged-decode entry
points itself; ``prefill_wrap`` / ``decode_wrap`` let callers interpose
on the compiled callables — the hook the VMM data plane uses to mediate
serving steps (benchmarks/fig6a measures that overhead).

``submit()`` returns a request id; ``future(rid)`` exposes a
``concurrent.futures.Future`` resolved with the finished ``Request``.
"""
from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.lock_watchdog import note_callback
from repro.core.mmu import MMUError
from repro.obs import (NULL_HUB, PHASE_ADMITTED, PHASE_DECODE,
                       PHASE_DEFERRED, PHASE_PREFILL, PHASE_PREFILL_CHUNK,
                       PHASE_REFAULT, PHASE_SWAP_OUT, span)
from repro.serving.paged_kv import PagedKVCache


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0            # 0 → greedy
    out_tokens: list = field(default_factory=list)
    done: bool = False

    def context(self) -> np.ndarray:
        """Prompt plus everything generated so far."""
        if not self.out_tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.out_tokens, np.int32)])


@dataclass
class EngineStats:
    steps: int = 0
    decode_steps: int = 0
    prefills: int = 0                   # one per admitted newcomer
    prefill_chunks: int = 0             # chunked-prefill chunk count
    full_prefills: int = 0              # paged engine: must stay 0
    admitted: int = 0
    deferred: int = 0                   # admissions bounced by the MMU
    completed: int = 0
    generated_tokens: int = 0
    # engine-local paging deltas (NOT the pool-global counters, which
    # also aggregate other engines sharing a tenant pool): leased counts
    # admission-time and demand-grown pages, so leased == freed once
    # every request has finished
    pages_leased: int = 0
    pages_freed: int = 0
    page_faults: int = 0
    # KV page hierarchy (engine-local deltas, same convention as above)
    shared_prefix_hits: int = 0         # warm admissions (prefix cache)
    shared_prefix_tokens: int = 0       # prompt tokens covered by sharing
    cow_forks: int = 0                  # private forks of shared pages
    swap_outs: int = 0                  # pages evicted to the host tier
    swap_ins: int = 0                   # pages refaulted back to device
    # paged recurrent state (PR 9): per-slot RWKV/RG-LRU rows leased
    # from the same pool as KV pages (engine-local deltas, as above)
    state_pages_leased: int = 0
    state_pages_freed: int = 0
    state_swap_outs: int = 0            # state pages parked to host
    state_swap_ins: int = 0             # state pages refaulted back


class ServeEngine:
    def __init__(self, cfg, model, batch_size: int, capacity: int,
                 page_size: int = 16, pool=None, auditor=None,
                 prefill_wrap: Optional[Callable] = None,
                 decode_wrap: Optional[Callable] = None,
                 extra_batch: Optional[dict] = None, eos_id: int = -1,
                 admission_gate: Optional[Callable] = None,
                 seed: int = 0, obs=None, obs_tenant: str = "serve",
                 chunk_tokens: int = 0, share_prefix: bool = False,
                 prefix_capacity_pages: Optional[int] = None,
                 swap: bool = False, transfer=None,
                 state_paging: bool = False, owner_prefix: str = ""):
        self.cfg = cfg
        self.model = model
        self.B = batch_size
        self.capacity = capacity
        self.extra_batch = extra_batch or {}
        self.eos_id = eos_id
        # chunked prefill (0 = off → monolithic admission): newcomers
        # are admitted immediately with a prefill cursor and each step
        # writes at most ``chunk_tokens`` of prompt into leased pages
        # while occupied slots keep decoding; the decode hot path then
        # runs fused (attention + on-device sampling, only (B,) token
        # ids leave the device). vlm/enc-dec frontends need the whole
        # prompt at once, so they stay monolithic.
        self.chunk_tokens = int(chunk_tokens)
        self._chunked = self.chunk_tokens > 0 and not self.extra_batch
        # prefix sharing rides on chunked prefill (a warm admission
        # starts the chunk cursor past the shared span — the monolithic
        # path has no cursor to start anywhere)
        self._share = share_prefix and self._chunked
        # swap tier: under admission pressure a victim slot is parked
        # (pages → host) instead of the newcomer being deferred/denied
        self._swap = swap and self._chunked
        self._parked: dict = {}           # slot → saved decode position
        # slots parked mid-step, after their token was emitted but
        # before its KV write: on resume that token feeds decode once
        # more for the write but must not be emitted twice
        self._emitted_parked: set = set()
        # telemetry hub: request-lifecycle spans (queued → admitted →
        # prefill → decode × N → done/deferred) land in obs.tracer under
        # the ``obs_tenant`` label; disabled hub → one attr check per site
        self.obs = obs if obs is not None else NULL_HUB
        self.obs_tenant = obs_tenant
        if self.obs.enabled:
            self.obs.registry.register_provider(
                f"engine/{obs_tenant}", lambda: dict(self.stats.__dict__))
        # admission-pressure hook: gate(owner, n_pages) -> bool. False
        # defers the newcomer (requeued at the front) instead of letting
        # the lease attempt bounce on MMUError — the knob a shared
        # tenant pool uses to keep serving admission pressure-aware.
        self.admission_gate = admission_gate
        self.rng = np.random.default_rng(seed)
        # concurrency: submission surface (waiting/_futures/_rid/
        # completed) is lock-guarded; the step path (slots, positions,
        # cursors, kv) is single-owner — exactly one driver thread calls
        # step()/run_round() at a time
        self._rid = 0                                  # guarded-by: _lock
        self.waiting: "collections.deque[Request]" = \
            collections.deque()                        # guarded-by: _lock
        self.completed: dict = {}                      # guarded-by: _lock
        self._futures: dict = {}                       # guarded-by: _lock
        self._lock = threading.Lock()
        self.stats = EngineStats()
        # per-slot decode state: positions (-1 = dead) + MMU-leased pages
        self.slots: List[Optional[Request]] = [None] * batch_size
        self.positions = np.full(batch_size, -1, np.int32)
        enc_len = (self.extra_batch["frames"].shape[1]
                   if "frames" in self.extra_batch else None)
        # when the engine auto-sizes its pool AND pages recurrent state,
        # size for the state rows too (KV working set alone would leave
        # recurrent-family admissions dead on arrival)
        extra_pages = 0
        if state_paging and pool is None \
                and hasattr(model, "state_row_bytes"):
            row_bytes = model.state_row_bytes()
            if row_bytes > 0:
                pb = model.kv_page_bytes(page_size)
                extra_pages = batch_size * max(1, -(-row_bytes // pb))
        self.kv = PagedKVCache(cfg, model, batch_size, capacity,
                               page_size=page_size, pool=pool,
                               auditor=auditor, enc_len=enc_len,
                               obs=self.obs, share_prefix=self._share,
                               prefix_capacity_pages=prefix_capacity_pages,
                               swap=self._swap, transfer=transfer,
                               extra_pages=extra_pages)
        # multi-engine pool sharing (model multiplexing): request owners
        # are namespaced per engine so two engines' rid spaces can never
        # collide into one MMU owner (quota/isolation would silently mix)
        self.owner_prefix = owner_prefix
        # paged recurrent state: per-slot RWKV/RG-LRU rows leased from
        # the same pool as the KV pages. Degrades to a no-op for
        # pure-attention models (state_row_bytes() == 0).
        self.rstate = None
        if state_paging and hasattr(model, "state_row_bytes"):
            from repro.serving.paged_state import PagedRecurrentState
            rs = PagedRecurrentState(cfg, model, batch_size,
                                     pool=self.kv.pool, obs=self.obs,
                                     transfer=transfer)
            self.rstate = rs if rs.enabled else None
        # chunked prefill reads a slot's recurrent rows as its initial
        # chunk state — a recycled slot must be zeroed at admission or
        # the newcomer reads the previous occupant's state
        self._row_reset_fn = None
        if self._chunked and getattr(model, "state_row_bytes",
                                     lambda: 0)() > 0:
            self._row_reset_fn = jax.jit(model.reset_state_row,
                                         donate_argnums=(0,))
        self._logits: Optional[np.ndarray] = None    # (B, V*) host copy
        # chunked-prefill bookkeeping: cursor = prompt tokens written so
        # far (-1 = not prefilling); _next = sampled-but-unemitted token
        # per slot (the fused decode path never ships logits to host)
        self._cursor = np.full(batch_size, -1, np.int64)
        self._next = np.zeros(batch_size, np.int64)
        self._rr = 0                     # chunk-scheduler rotation
        pf = jax.jit(lambda p, b: model.prefill(p, b))
        df = jax.jit(model.decode_paged, donate_argnums=(1,))
        cf = jax.jit(model.prefill_chunk_paged, donate_argnums=(1,))
        ff = jax.jit(model.decode_paged_fused, donate_argnums=(1,))
        self._prefill_fn = prefill_wrap(pf) if prefill_wrap else pf
        self._decode_fn = decode_wrap(df) if decode_wrap else df
        self._chunk_fn = prefill_wrap(cf) if prefill_wrap else cf
        self._fused_fn = decode_wrap(ff) if decode_wrap else ff

    # ------------------------------------------------------------------
    def submit(self, prompt_tokens, max_new_tokens=16, temperature=0.0):
        prompt = np.asarray(prompt_tokens, np.int32)
        if len(prompt) > self.capacity:
            raise ValueError(f"prompt of {len(prompt)} tokens exceeds "
                             f"KV capacity {self.capacity}")
        # one critical section: rid assignment, future registration, and
        # the waiting-queue append must be atomic so FIFO admission
        # order always matches rid order under concurrent submitters
        with self._lock:
            rid = self._rid
            self._rid += 1
            self._futures[rid] = Future()
            self.waiting.append(Request(rid, prompt, max_new_tokens,
                                        temperature))
        if self.obs.enabled:
            self.obs.tracer.start(self.obs_tenant, rid,
                                  prompt_len=len(prompt),
                                  max_new_tokens=max_new_tokens)
        return rid

    def future(self, rid: int) -> Future:
        """Completion future for a submitted request id."""
        with self._lock:
            return self._futures[rid]

    def has_work(self) -> bool:
        with self._lock:
            return (bool(self.waiting)
                    or any(r is not None for r in self.slots))

    # ------------------------------------------------------------------
    # Admission: prefill the newcomer alone into freshly leased pages
    # ------------------------------------------------------------------
    def _newcomer_batch(self, slot: int, req: Request):
        batch = {"tokens": jnp.asarray(req.prompt[None])}
        for k, v in self.extra_batch.items():         # vlm patches / frames
            batch[k] = jnp.asarray(v)[slot:slot + 1]
        return batch

    def _admit(self, params):
        with span("engine.admit"):
            self._admit_body(params)

    def _admit_body(self, params):
        for i in range(self.B):
            if self.slots[i] is not None:
                continue
            with self._lock:
                if not self.waiting:
                    break
                req = self.waiting.popleft()
            owner = f"{self.owner_prefix}req{req.rid}"
            plen = len(req.prompt)
            # chunked: the admission ask is one chunk's pages, later
            # chunks fault the rest of the table in incrementally
            lease_len = (min(plen, self.chunk_tokens) if self._chunked
                         else plen)
            n_pages = max(1, -(-lease_len // self.kv.page_size))
            if self.rstate is not None:
                n_pages += self.rstate.blocks_per_slot
            live = any(s is not None for s in self.slots)
            if self.admission_gate is not None:
                note_callback("engine.admission_gate")
            gated = (self.admission_gate is not None and live
                     and not self.admission_gate(owner, n_pages))
            if gated and self._swap and self._swap_out_victim():
                # swap-before-deny: parking a victim freed its private
                # pages — re-ask the gate before deferring the newcomer
                gated = not self.admission_gate(owner, n_pages)
            if gated:
                # pool pressure: defer the newcomer before touching the
                # MMU. Advisory only — with no live slot (nothing will
                # ever free a page) we fall through to the lease attempt
                # so true exhaustion still surfaces as MMUError below.
                self.stats.deferred += 1
                if self.obs.enabled:
                    self.obs.tracer.event(self.obs_tenant, req.rid,
                                          PHASE_DEFERRED,
                                          cause="pool_pressure")
                with self._lock:
                    self.waiting.appendleft(req)
                break
            prompt = req.prompt if self._share else None
            try:
                try:
                    shared = self.kv.admit(i, owner, plen,
                                           lease_len=lease_len,
                                           prompt=prompt)
                except MMUError:
                    # swap-before-deny, MMU flavor: the lease bounced on
                    # a dry pool — park a victim and retry once
                    if not (self._swap and self._swap_out_victim()):
                        raise
                    shared = self.kv.admit(i, owner, plen,
                                           lease_len=lease_len,
                                           prompt=prompt)
            except MMUError as exc:
                # pool exhausted / quota: requeue at the front, retry
                # next step once EOS recycling returns pages
                self.stats.deferred += 1
                if self.obs.enabled:
                    self.obs.tracer.event(self.obs_tenant, req.rid,
                                          PHASE_DEFERRED,
                                          cause=type(exc).__name__)
                with self._lock:
                    self.waiting.appendleft(req)
                if all(s is None for s in self.slots):
                    # no live slot will ever free a page — surface the
                    # exhaustion instead of busy-spinning run_round()
                    raise
                break
            if self.rstate is not None:
                # the slot's recurrent-state pages lease from the same
                # pool, under the same deferral/swap-relief story
                try:
                    try:
                        self.rstate.admit(i, owner)
                    except MMUError:
                        if not (self._swap and self._swap_out_victim()):
                            raise
                        self.rstate.admit(i, owner)
                except MMUError as exc:
                    self.stats.pages_freed += self.kv.tables[i].n_pages
                    self.kv.release(i)
                    self.stats.deferred += 1
                    if self.obs.enabled:
                        self.obs.tracer.event(self.obs_tenant, req.rid,
                                              PHASE_DEFERRED,
                                              cause=type(exc).__name__)
                    with self._lock:
                        self.waiting.appendleft(req)
                    if all(s is None for s in self.slots):
                        raise
                    break
                self.stats.state_pages_leased += self.rstate.blocks_per_slot
            if self._row_reset_fn is not None:
                with span("engine.row_reset", slot=i):
                    self.kv.state = self._row_reset_fn(self.kv.state,
                                                       np.int32(i))
            if shared:
                self.stats.shared_prefix_hits += 1
                self.stats.shared_prefix_tokens += shared
            if self.obs.enabled:
                self.obs.tracer.event(self.obs_tenant, req.rid,
                                      PHASE_ADMITTED, slot=i,
                                      pages=self.kv.tables[i].n_pages,
                                      shared_tokens=shared)
            if self._chunked:
                # admitted immediately with a prefill cursor; the chunk
                # scheduler writes the prompt across subsequent steps
                # while occupied slots keep decoding. positions stays -1
                # (dead for decode) until the last chunk lands. A warm
                # admission starts past the shared span — those tokens'
                # KV pages are already resident and mapped.
                self.slots[i] = req
                self.positions[i] = -1
                self._cursor[i] = shared
                self.stats.admitted += 1
                self.stats.pages_leased += self.kv.tables[i].n_pages
                continue
            logits, caches = self._prefill_fn(
                params, self._newcomer_batch(i, req))
            self.kv.write_prefill(caches, i, plen)
            if self.obs.enabled:
                self.obs.tracer.event(self.obs_tenant, req.rid,
                                      PHASE_PREFILL, tokens=plen)
            with span("engine.fetch"):
                logits = np.asarray(jax.device_get(logits), np.float32)
            if self._logits is None:
                self._logits = np.zeros((self.B, logits.shape[-1]),
                                        np.float32)
            self._logits[i] = logits[0]
            self.slots[i] = req
            self.positions[i] = plen                  # next write position
            self.stats.admitted += 1
            self.stats.prefills += 1
            self.stats.pages_leased += self.kv.tables[i].n_pages

    # ------------------------------------------------------------------
    # Stepping
    # ------------------------------------------------------------------
    # ------------------------------------------------------------------
    # Chunked prefill: bounded prompt writes interleaved with decode
    # ------------------------------------------------------------------
    def _sample_one(self, logits, temperature):
        """Host-side sample of one token from (V*,) logits — used once
        per request, for the first token after the last prefill chunk."""
        lg = logits[:self.cfg.vocab]
        if temperature <= 0.0:
            return int(np.argmax(lg))
        g = self.rng.gumbel(size=lg.shape[0])
        return int(np.argmax(lg / temperature + g))

    def _abort_prefill(self, i, exc):
        """A chunk's page fault bounced on the MMU mid-prefill: release
        everything written so far and requeue the request at the front —
        it restarts from token 0 once EOS recycling returns pages."""
        req = self.slots[i]
        self.stats.deferred += 1
        self.stats.pages_freed += self.kv.tables[i].n_pages
        self.kv.release(i)
        self._release_state(i)
        self.slots[i] = None
        self.positions[i] = -1
        self._cursor[i] = -1
        with self._lock:
            self.waiting.appendleft(req)
        if self.obs.enabled:
            self.obs.tracer.event(self.obs_tenant, req.rid, PHASE_DEFERRED,
                                  cause=f"{type(exc).__name__}_mid_prefill")
        if all(s is None for s in self.slots):
            # nothing live will ever free a page — surface the
            # exhaustion instead of re-admitting into the same wall
            raise exc

    def _prefill_chunks(self, params):
        """One step's chunk budget: write at most ``chunk_tokens`` of
        prompt across the slots that are mid-prefill, round-robin (the
        rotation point advances every step so concurrent newcomers share
        the budget fairly). Chunks are never split below
        min(chunk_tokens, remaining) — the compile universe stays one
        shape per (chunk_tokens, prompt_len % chunk_tokens) pair."""
        with span("engine.prefill_chunks"):
            self._prefill_chunks_body(params)

    def _prefill_chunks_body(self, params):
        prefilling = [i for i in range(self.B)
                      if self.slots[i] is not None and self._cursor[i] >= 0]
        if not prefilling:
            return
        budget = self.chunk_tokens
        rot = self._rr % len(prefilling)
        self._rr += 1
        for i in prefilling[rot:] + prefilling[:rot]:
            req = self.slots[i]
            plen = len(req.prompt)
            start = int(self._cursor[i])
            c = min(self.chunk_tokens, plen - start)
            if c > budget:
                break
            budget -= c
            before = self.kv.tables[i].n_pages
            try:
                # incremental leasing: fault in the pages this chunk
                # spans (admission only leased the first chunk's worth).
                # write_from=start makes the whole chunk window privately
                # writable — a warm request writing past its shared span
                # into a partially-filled shared page CoW-forks it here.
                with span("kv.ensure", rid=req.rid, start=start):
                    self.kv.ensure(i, start + c - 1, write_from=start)
                grown = self.kv.tables[i].n_pages - before
                self.stats.page_faults += grown
                self.stats.pages_leased += grown
            except MMUError as exc:
                grown = self.kv.tables[i].n_pages - before
                self.stats.page_faults += grown
                self.stats.pages_leased += grown
                self._abort_prefill(i, exc)
                continue
            with span("engine.inputs"):
                with span("engine.block_tables"):
                    bt = jnp.asarray(self.kv.block_tables()[i])
                args = (jnp.asarray(req.prompt[None, start:start + c]),
                        jnp.int32(i), bt, jnp.int32(start))
            logits, self.kv.state = self._chunk_fn(params, self.kv.state,
                                                   *args)
            self._cursor[i] = start + c
            self.stats.prefill_chunks += 1
            if self.obs.enabled:
                self.obs.tracer.event(self.obs_tenant, req.rid,
                                      PHASE_PREFILL_CHUNK, tokens=c,
                                      start=start)
                self.obs.observe("serve_prefill_chunk_tokens", c,
                                 tenant=self.obs_tenant)
            if start + c >= plen:
                # prefill complete: sample the first token from the last
                # chunk's logits (the one host round-trip per request),
                # then the slot joins the fused decode batch
                with span("engine.fetch"):
                    lg = np.asarray(jax.device_get(logits), np.float32)[0]
                self._next[i] = self._sample_one(lg, req.temperature)
                self._cursor[i] = -1
                self.positions[i] = plen
                self.stats.prefills += 1
                if self._share:
                    # publish the finished prompt's pages so future
                    # requests with this prefix admit warm
                    self.kv.register_prefix(i, req.prompt)
                if self.obs.enabled:
                    self.obs.tracer.event(self.obs_tenant, req.rid,
                                          PHASE_PREFILL, tokens=plen)

    def _release_state(self, i: int):
        """Return slot ``i``'s recurrent-state pages (no-op without
        paged state)."""
        if self.rstate is None or self.rstate.tables[i] is None:
            return
        self.stats.state_pages_freed += self.rstate.tables[i].n_pages
        self.rstate.release(i)

    # ------------------------------------------------------------------
    # Swap tier: park a victim slot under pressure, resume when calm
    # ------------------------------------------------------------------
    def _swap_out_victim(self, exclude=None, mid_step: bool = False
                         ) -> bool:
        """Suspend one decoding slot: move its private pages to the host
        tier and mark it parked (positions → -1, saved for resume). The
        victim is the decoder holding the most pages — the biggest
        single relief. Returns True if any pages actually moved."""
        candidates = [j for j in range(self.B)
                      if self.slots[j] is not None and j != exclude
                      and j not in self._parked
                      and self.positions[j] >= 0 and self._cursor[j] < 0]
        candidates.sort(key=lambda j: self.kv.tables[j].n_pages,
                        reverse=True)
        for j in candidates:
            if self._park(j, mid_step=mid_step):
                return True
        return False

    def _park(self, j: int, mid_step: bool = False) -> bool:
        """Suspend slot ``j``: private KV pages and recurrent-state rows
        to the host tier, decode position saved. False if nothing moved
        (fully shared slot with no recurrent state)."""
        moved = self.kv.swap_out(j)
        smoved = 0
        if self.rstate is not None:
            self.kv.state, smoved = self.rstate.park(self.kv.state, j)
        if moved == 0 and smoved == 0:
            return False                 # fully shared slot: no relief
        self._parked[j] = int(self.positions[j])
        if mid_step:
            self._emitted_parked.add(j)
        self.positions[j] = -1
        self.stats.swap_outs += moved
        self.stats.state_swap_outs += smoved
        if self.obs.enabled:
            self.obs.tracer.event(self.obs_tenant, self.slots[j].rid,
                                  PHASE_SWAP_OUT, pages=moved,
                                  state_pages=smoved)
            self.obs.flight_record(
                self.obs_tenant, "kv_swap_out",
                {"slot": j, "pages": moved, "state_pages": smoved,
                 "rid": self.slots[j].rid})
        return True

    def _try_resume(self):
        """Refault the oldest parked slot back in once the pool can hold
        it again. Newcomers keep priority: while the queue is non-empty
        and a free slot exists, the pages go to admissions first —
        mid-decode ensure() truncation guarantees forward progress, so
        parked slots can never deadlock the engine."""
        if not self._parked:
            return
        with self._lock:
            waiting = bool(self.waiting)
        if waiting and any(s is None for s in self.slots):
            return
        ms = self.kv.pool.memory_stats()
        free = ms["segments_total"] - ms["segments_in_use"]
        idle = not waiting and all(
            self.slots[j] is None or j in self._parked
            for j in range(self.B))
        for j in sorted(self._parked):
            need = self.kv.swapped_blocks(j)
            if self.rstate is not None:
                need += self.rstate.swapped_blocks(j)
            # reserve the growth page when the pending write position
            # sits past the table — resuming into an exactly-full pool
            # would re-park the slot at once without emitting anything
            if (self._parked[j] // self.kv.page_size
                    >= self.kv.tables[j].n_pages):
                need += 1
            if need > free:
                if not (idle and self.kv.prefix is not None
                        and len(self.kv.prefix)):
                    continue
                # only parked slots remain and prefix-cache pins hold
                # the pool: shed them — liveness beats cache warmth
                self.kv.prefix.evict_all()
                ms = self.kv.pool.memory_stats()
                free = ms["segments_total"] - ms["segments_in_use"]
                if need > free:
                    continue
            n = self.kv.swap_in(j)
            sn = 0
            if self.rstate is not None:
                self.kv.state, sn = self.rstate.refault(self.kv.state, j)
            self.positions[j] = self._parked.pop(j)
            self.stats.swap_ins += n
            self.stats.state_swap_ins += sn
            if self.obs.enabled:
                self.obs.tracer.event(self.obs_tenant, self.slots[j].rid,
                                      PHASE_REFAULT, pages=n,
                                      state_pages=sn)
                self.obs.flight_record(
                    self.obs_tenant, "kv_refault",
                    {"slot": j, "pages": n, "state_pages": sn,
                     "rid": self.slots[j].rid})
            return                       # one resume per step

    def _finish(self, i, finished):
        r = self.slots[i]
        r.done = True
        self._parked.pop(i, None)
        self._emitted_parked.discard(i)
        self.slots[i] = None                      # recycle the slot
        self.positions[i] = -1
        self._cursor[i] = -1
        self.stats.pages_freed += self.kv.tables[i].n_pages
        self.kv.release(i)                        # pages back to the MMU
        self._release_state(i)
        with self._lock:
            self.completed[r.rid] = r
            fut = self._futures.get(r.rid)
        self.stats.completed += 1
        finished.append(r)
        if self.obs.enabled:
            self.obs.tracer.finish(self.obs_tenant, r.rid, "done",
                                   tokens=len(r.out_tokens))
        # resolve OUTSIDE the lock: set_result runs done-callbacks (user
        # code) on this thread
        if fut is not None and not fut.done():
            fut.set_result(r)

    def step(self, params) -> List[Request]:
        """One engine step: admit waiting requests into free slots (each
        prefilled alone into its own pages), emit one token per active
        slot, recycle EOS/budget-exhausted slots, advance decode with
        per-slot positions. Returns the requests that finished."""
        if not self.obs.enabled:
            with span("engine.step"):
                return self._step(params)
        t0 = time.perf_counter()
        with span("engine.step"):
            finished = self._step(params)
        self.obs.observe("engine_step_s", time.perf_counter() - t0,
                         tenant=self.obs_tenant)
        return finished

    def _step(self, params) -> List[Request]:
        # CoW forks fire inside kv.ensure() at several call sites; take
        # the per-step delta so ``eng.stats = EngineStats()`` resets
        # cleanly (the benchmark idiom) while kv keeps monotonic counts
        cf0 = self.kv.cow_forks
        try:
            return self._step_body(params)
        finally:
            self.stats.cow_forks += self.kv.cow_forks - cf0

    def _step_body(self, params) -> List[Request]:
        finished: List[Request] = []
        self._admit(params)
        if self._chunked:
            self._prefill_chunks(params)
        if self._swap:
            self._try_resume()
        # mid-prefill slots (positions -1) occupy a slot but don't emit
        active = [i for i in range(self.B) if self.slots[i] is not None
                  and self.positions[i] >= 0]
        if not active:
            return finished
        self.stats.steps += 1
        with span("engine.emit"):
            nxt = (self._next if self._chunked
                   else self._sample(self._logits, active))
            token = np.zeros((self.B, 1), np.int32)
            for i in active:
                r = self.slots[i]
                if i in self._emitted_parked:
                    # first step after a mid-step park resumed: _next[i] was
                    # already emitted in the step that parked this slot —
                    # feed it to decode for its pending KV write, once,
                    # without emitting it a second time
                    self._emitted_parked.discard(i)
                    token[i, 0] = int(nxt[i])
                    continue
                if len(r.out_tokens) >= r.max_new_tokens:   # zero-budget case
                    self._finish(i, finished)
                    continue
                tok = int(nxt[i])
                r.out_tokens.append(tok)
                self.stats.generated_tokens += 1
                if self.obs.enabled:
                    self.obs.tracer.token(self.obs_tenant, r.rid)
                token[i, 0] = tok
                if tok == self.eos_id or len(r.out_tokens) >= r.max_new_tokens:
                    self._finish(i, finished)
                elif self.positions[i] >= self.capacity:
                    self._finish(i, finished)           # KV budget: truncate
        # one span over every decoding slot's demand paging, not one per
        # slot
        with span("kv.ensure"):
            for i in [i for i in range(self.B) if self.slots[i] is not None
                      and self.positions[i] >= 0]:
                if self.positions[i] < 0:
                    continue      # parked by an earlier slot's swap relief
                # demand paging — counters track engine-local deltas, never
                # the pool-global ones (a shared --virtualized tenant pool
                # serves other engines too); demand-grown pages count as
                # leased so pages_leased/pages_freed balance at EOS
                before = self.kv.tables[i].n_pages
                try:
                    try:
                        self.kv.ensure(i, int(self.positions[i]))
                    except MMUError:
                        if not self._swap:
                            raise
                        # swap relief: park another decoder so this slot's
                        # page fault can be served; with no other decoder to
                        # shed, suspend this slot itself — it resumes (and
                        # completes its pending KV write) once pages free up
                        if self._swap_out_victim(exclude=i, mid_step=True):
                            self.kv.ensure(i, int(self.positions[i]))
                        elif self._park(i, mid_step=True):
                            continue
                        else:
                            raise
                    grown = self.kv.tables[i].n_pages - before
                    self.stats.page_faults += grown
                    self.stats.pages_leased += grown
                except MMUError:
                    # a shared pool ran dry mid-decode: truncate this slot
                    # (its sampled tokens are already delivered) rather than
                    # wedge the whole batch — pages grown before the failure
                    # are still accounted before _finish frees the table
                    grown = self.kv.tables[i].n_pages - before
                    self.stats.page_faults += grown
                    self.stats.pages_leased += grown
                    self._finish(i, finished)
        remaining = [i for i in range(self.B) if self.slots[i] is not None
                     and self.positions[i] >= 0]
        if not remaining:
            return finished
        self.stats.decode_steps += 1
        if self._chunked:
            # fused decode: paged attention + on-device sampling — only
            # the (B,) sampled token ids cross to host, not (B, V) logits
            temps = np.zeros(self.B, np.float32)
            for i in remaining:
                temps[i] = self.slots[i].temperature
            with span("engine.inputs"):
                with span("engine.block_tables"):
                    bt = jnp.asarray(self.kv.block_tables())
                args = (jnp.asarray(token), jnp.asarray(self.positions), bt,
                        jnp.asarray(temps), jnp.int32(self.stats.steps))
            toks, self.kv.state = self._fused_fn(params, self.kv.state,
                                                 *args)
            with span("engine.fetch"):
                toks = np.asarray(jax.device_get(toks))
            for i in remaining:
                self._next[i] = int(toks[i])
        else:
            with span("engine.inputs"):
                with span("engine.block_tables"):
                    bt = jnp.asarray(self.kv.block_tables())
                args = (jnp.asarray(token), jnp.asarray(self.positions), bt)
            logits, self.kv.state = self._decode_fn(params, self.kv.state,
                                                    *args)
            with span("engine.fetch"):
                self._logits = np.asarray(jax.device_get(logits),
                                          np.float32)
        if self.obs.enabled:
            for i in remaining:
                self.obs.tracer.event(self.obs_tenant, self.slots[i].rid,
                                      PHASE_DECODE)
        for i in remaining:
            self.positions[i] += 1
        return finished

    def run_round(self, params) -> List[Request]:
        """Drain: step until nothing is waiting or in-flight. Admission
        also happens *between* steps, so late ``submit()``s join
        mid-round."""
        finished: List[Request] = []
        while self.has_work():
            finished.extend(self.step(params))
        return finished

    # ------------------------------------------------------------------
    def _sample(self, logits, rows):
        """Vectorized per-row sampling: one argmax for every greedy row;
        temperature rows via the Gumbel-max trick (argmax of scaled
        logits + Gumbel noise ≡ softmax sampling) — no Python loop on
        the per-token hot path."""
        V = self.cfg.vocab
        lg = logits[:, :V]
        out = np.argmax(lg, axis=-1).astype(np.int64)
        temps = np.zeros(logits.shape[0])
        for i in rows:
            temps[i] = self.slots[i].temperature
        hot = [i for i in rows if temps[i] > 0.0]
        if hot:
            g = self.rng.gumbel(size=(len(hot), V))
            scaled = lg[hot] / temps[hot][:, None] + g
            out[hot] = np.argmax(scaled, axis=-1)
        return out


def pool_pressure_gate(pool, util_hwm: float = 0.9,
                       headroom_pages: int = 0) -> Callable:
    """Admission-pressure hook over a shared ``SegmentPool``.

    Returns ``gate(owner, n_pages) -> bool`` for ``ServeEngine``'s
    ``admission_gate``: admit only while the pool can cover the ask plus
    ``headroom_pages`` AND *post-admission* occupancy stays at or under
    ``util_hwm`` — gating on current occupancy would let one large ask
    fill the pool outright and re-create the mid-decode ``MMUError``
    truncation this hook exists to prevent. Under pressure the engine
    defers the newcomer (it retries once EOS recycling returns pages).
    """
    def gate(owner: str, n_pages: int) -> bool:
        ms = pool.memory_stats()
        total = max(ms["segments_total"], 1)
        free = ms["segments_total"] - ms["segments_in_use"]
        util_after = (ms["segments_in_use"] + n_pages) / total
        return free >= n_pages + headroom_pages and util_after <= util_hwm
    return gate
