"""Plain float32 references of the benchmark's model families.

Each module here is named by a configuration file's ``reference`` key
and holds ``logits(w, cfg, tokens, positions_out, dot)``: the next-token
logits at ``positions_out`` of one sequence ``tokens``, computed from
the weights ``w`` in straightforward ``jax.numpy``. They import nothing
of the program under test. ``dot`` is the matrix product to use:
``dot_f32`` for the reference, a lower-precision one for the control.

A family module may also declare what the rest of the benchmark needs
to know of the family:

- ``WEIGHT_RULES``, ``WEIGHT_RANK``: rules for the leaves of its weights
  that ``weights.RULES`` lacks (``weights.joined``);
- ``REFERENCE_ONLY``: keys of its configuration files that the reference
  and the operation counts read and the program takes no setting for
  (``configs.model_config`` leaves them out).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import found


def load(family: str, bench=found.BENCH):
    """The module ``reference/<family>.py`` of benchmark directory
    ``bench``."""
    return found.module("reference", family, bench)


def dot_f32(a, b):
    """float32 product at full precision (the reference)."""
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def dot_fp8(a, b):
    """Both operands rounded to float8 e4m3 (a per-tensor scale keeps
    them in range), accumulated in float32: the control, one precision
    step below the configuration's bfloat16."""
    def q(x):
        x = x.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s
    qa, sa = q(a)
    qb, sb = q(b)
    return jnp.matmul(qa, qb, precision=jax.lax.Precision.HIGHEST) * (sa * sb)


def layer_slice(tree, i):
    """Layer ``i`` of a tree of layer-stacked arrays."""
    return jax.tree.map(lambda a: a[i], tree)
