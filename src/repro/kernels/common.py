"""Shared kernel utilities: kernel-path choice, interpret mode, grid helpers.

The platform picks the path: the model runs its Pallas kernels on the TPU
backend and its XLA paths elsewhere. Pallas runs in interpret mode only
on the CPU backend, where tests check the kernels against their oracles.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

#: the innermost ``kernel_path`` choice of this thread/context, or None
_forced = contextvars.ContextVar("kernel_path", default=None)


def use_pallas() -> bool:
    """True where the model should trace its Pallas kernels: on the TPU
    backend, unless a ``kernel_path`` block says otherwise."""
    forced = _forced.get()
    if forced is not None:
        return forced
    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def kernel_path(pallas: bool):
    """Trace the model's Pallas kernels (``True``) or its XLA paths
    (``False``) inside this block, whatever the platform. The choice is
    read when a function is traced, so a jitted function must be first
    called inside the block. For comparing the two paths, not serving."""
    token = _forced.set(bool(pallas))
    try:
        yield
    finally:
        _forced.reset(token)


def use_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode on the CPU backend."""
    return jax.default_backend() == "cpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
