"""Reconfiguration — the PR-controller analogue.

FPGA partial reconfiguration ↔ loading a freshly-compiled XLA executable
onto a vSlice. The mapping (DESIGN.md §2):

* bitfile            → ``Bitfile``: AOT-compiled executable + metadata
* CRC check          → content fingerprint verified at load
* decode + PR flow   → ``ProgramLoader.load`` with the freeze protocol
* bitfile↔PRR check  → slice binding: a Bitfile records the topology class
  and concrete slice fingerprint it was compiled for; the VMM refuses a
  load whose binding does not match the caller's slice (the paper's
  "user in VM0 reprograms PRR1" attack), while allowing *re-binding*
  across identical-topology slices via recompile-free device reassignment
  when permitted (warm migration).
* 2.5 s PCIe reconfig cost → XLA compile seconds; the ``CompileService``
  cache turns repeat loads into warm (milliseconds) reconfigurations.
"""
from __future__ import annotations

import contextlib
import hashlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core.vslice import VSlice


class ReconfigError(Exception):
    pass


class LegalityError(ReconfigError):
    """Bitfile↔slice legality violation (isolation criterion)."""


@dataclass
class ProgramRequest:
    """What a tenant asks to have 'flashed': a named step program."""
    arch: str
    kind: str                    # 'train' | 'prefill' | 'decode'
    seq_len: int
    global_batch: int
    reduced: bool = True
    opt_flags: Tuple = ()

    @property
    def program_key(self) -> str:
        h = hashlib.sha256(repr((self.arch, self.kind, self.seq_len,
                                 self.global_batch, self.reduced,
                                 self.opt_flags)).encode())
        return h.hexdigest()[:16]


@dataclass
class Bitfile:
    program_key: str
    topology_key: str            # e.g. "2x4" — shape class compatibility
    slice_fingerprint: str       # concrete binding
    compiled: object             # jax compiled executable
    abstract_args: tuple
    crc: str = ""
    compile_seconds: float = 0.0

    def __post_init__(self):
        if not self.crc:
            self.crc = self._compute_crc()

    def _compute_crc(self) -> str:
        h = hashlib.sha256(
            f"{self.program_key}|{self.topology_key}|"
            f"{self.slice_fingerprint}".encode())
        return h.hexdigest()[:16]

    def verify_crc(self) -> bool:
        return self.crc == self._compute_crc()


def weights_fingerprint(params) -> str:
    """Content hash of a weights pytree — leaf paths, shapes, dtypes and
    bytes. This is the ``slice_fingerprint`` of a weights-as-bitstream
    :class:`Bitfile` (model multiplexing): the CRC commits to the actual
    parameter bytes, so host-tier corruption of a swapped-out model is
    caught at swap-in, not silently served."""
    h = hashlib.blake2b(digest_size=8)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in leaves:
        arr = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


@dataclass
class LoadedProgram:
    bitfile: Bitfile
    slice_id: int

    def __call__(self, *args):
        return self.bitfile.compiled(*args)


class CompileService:
    """AOT lower+compile against a slice mesh, with an executable cache.

    Cache key = (program_key, slice fingerprint): an XLA executable is
    bound to the devices it was compiled for, so a warm hit is the same
    program re-flashed onto the same slice. Another slice of the same
    topology class compiles its own: handing it the first slice's
    executable would run the tenant on its neighbour's chips."""

    def __init__(self, step_builder: Optional[Callable] = None):
        # step_builder(cfg, mesh, cell) → (jitted, abstract_args)
        if step_builder is None:
            from repro.parallel.steps import build_step_for_cell
            step_builder = build_step_for_cell
        self._build = step_builder
        self.cache: Dict[Tuple[str, str], Bitfile] = {}
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()

    def compile(self, req: ProgramRequest, vslice: VSlice) -> Bitfile:
        key = (req.program_key, vslice.fingerprint)
        with self._lock:
            if key in self.cache:
                self.hits += 1
                cached = self.cache[key]
                return Bitfile(cached.program_key, cached.topology_key,
                               cached.slice_fingerprint, cached.compiled,
                               cached.abstract_args,
                               compile_seconds=0.0)
        from repro.configs import get_config
        from repro.configs.base import ShapeCell
        cfg = get_config(req.arch, reduced=req.reduced)
        cell = ShapeCell("custom", req.seq_len, req.global_batch,
                         req.kind)
        t0 = time.perf_counter()
        mesh = getattr(vslice, "mesh", None)
        ctx = (jax.set_mesh(mesh) if mesh is not None
               else contextlib.nullcontext())
        with ctx:
            jitted, abstract_args = self._build(cfg, mesh, cell)
            lowered = jitted.lower(*abstract_args)
            compiled = lowered.compile()
        dt = max(time.perf_counter() - t0, 1e-9)
        bf = Bitfile(req.program_key, vslice.topology_key,
                     vslice.fingerprint, compiled, abstract_args,
                     compile_seconds=dt)
        with self._lock:
            self.misses += 1
            self.cache[key] = bf
        return bf


class ProgramLoader:
    """The PR flow: legality checks + freeze protocol + load."""

    def __init__(self, auditor=None):
        self.loaded: Dict[int, LoadedProgram] = {}   # slice_id → program
        self.auditor = auditor
        self.reconfigs = 0
        self.crc_checks = 0
        self.crc_failures = 0

    def verify_bitfile(self, bitfile: Bitfile, owner: str = "?"):
        """CRC-only verification (counted) — every load AND every
        model-registry swap-in goes through here, so a corrupted
        bitstream never reaches a slice or a serving engine silently."""
        self.crc_checks += 1
        if not bitfile.verify_crc():
            self.crc_failures += 1
            if self.auditor:
                self.auditor.record("bitfile_crc_fail", owner, {})
            raise LegalityError("bitfile CRC check failed")

    def validate(self, bitfile: Bitfile, vslice: VSlice, owner: str = "?"):
        self.verify_bitfile(bitfile, owner)
        if bitfile.topology_key != vslice.topology_key:
            if self.auditor:
                self.auditor.record("bitfile_topology_mismatch", owner,
                                    {"bitfile": bitfile.topology_key,
                                     "slice": vslice.topology_key})
            raise LegalityError(
                f"bitfile for topology {bitfile.topology_key} cannot load "
                f"on slice {vslice.topology_key}")
        if bitfile.slice_fingerprint != vslice.fingerprint:
            if self.auditor:
                self.auditor.record("cross_slice_reprogram", owner,
                                    {"bitfile_slice":
                                     bitfile.slice_fingerprint,
                                     "target_slice": vslice.fingerprint})
            raise LegalityError(
                "bitfile is bound to a different slice (the paper's "
                "cross-PRR reprogram attack) — VMM must re-bind it")

    def load(self, bitfile: Bitfile, vslice: VSlice, quiesce: Callable,
             owner: str = "?") -> LoadedProgram:
        self.validate(bitfile, vslice, owner)
        # freeze protocol: drain + block the slice while swapping programs
        with quiesce():
            prog = LoadedProgram(bitfile, vslice.slice_id)
            self.loaded[vslice.slice_id] = prog
            self.reconfigs += 1
        return prog

    def unload(self, vslice: VSlice):
        self.loaded.pop(vslice.slice_id, None)
