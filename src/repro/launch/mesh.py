"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256-chip v5e pod; multi-pod = 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types: the partitioner propagates
    shardings through ops such as the embedding gather, which Explicit
    axes (``jax.make_mesh``'s default) would reject."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto, devices=devices)


def make_local_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (CPU sim / tests)."""
    import numpy as np
    n = len(jax.devices())
    if shape is None:
        shape = (1, n)
    size = int(np.prod(shape))
    assert size <= n, (shape, n)
    return _auto_mesh(shape, axes, jax.devices()[:size])
