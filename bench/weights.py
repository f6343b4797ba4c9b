"""Random weights from the seed, made by the benchmark and not by the
program, so that the reference can read them too.

The tree has the program's layout (taken from ``jax.eval_shape`` of its
``init``, no values), and every leaf is drawn by the rule for its name
below in one jitted call on the device, in the dtype the program keeps
its parameters in. A name with no rule is an error.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: leaf name → (rule, argument). ``fan_in``: normal / sqrt(product of the
#: leaf's first ``argument`` axes, counted after any layer-stack axis);
#: ``normal``: normal times ``argument``; ``const``: filled with it;
#: ``around``: ``argument`` plus 0.02 times a normal; ``uniform``: over
#: the interval ``argument``.
RULES = {
    "tok_embed": ("normal", 0.02), "lm_head": ("normal", 0.02),
    "scale": ("const", 1.0), "bias": ("const", 0.0),
    "ln_scale": ("const", 1.0), "ln_bias": ("const", 0.0),
    # attention (d, H, hd) in, (H, hd, d) out
    "wq": ("fan_in", 1), "wk": ("fan_in", 1), "wv": ("fan_in", 1),
    "wo": ("fan_in", 2),
    # SwiGLU
    "w_gate": ("fan_in", 1), "w_up": ("fan_in", 1), "w_down": ("fan_in", 1),
    # RWKV-6 time-mix and channel-mix
    "w_r": ("fan_in", 1), "w_k": ("fan_in", 1), "w_v": ("fan_in", 1),
    "w_g": ("fan_in", 1), "w_o": ("fan_in", 1),
    "mix_A": ("fan_in", 1), "mix_B": ("normal", 0.02),
    "decay_A": ("fan_in", 1), "decay_B": ("fan_in", 1),
    "mu_base": ("const", 0.5), "mu_k": ("const", 0.5),
    "mu_r": ("const", 0.5), "mu_rkvwg": ("around", 0.5),
    "decay_base": ("uniform", (-7.0, 1.0)), "bonus_u": ("normal", 0.02),
}

#: the rank of each leaf as the program lays out one layer's weights
RANK = {"wq": 3, "wk": 3, "wv": 3, "wo": 3, "mix_B": 3, "mu_rkvwg": 2,
        "bonus_u": 2}


def seed_key(seed: int):
    """A PRNG key from any whole number ≥ 0, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    return str(path[-1].key)


def _draw(name, sds, key):
    rule, arg = RULES[name]
    shape, dtype = sds.shape, sds.dtype
    if rule == "const":
        return jnp.full(shape, arg, dtype)
    if rule == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, *arg).astype(dtype)
    z = jax.random.normal(key, shape, jnp.float32)
    if rule == "normal":
        return (z * arg).astype(dtype)
    if rule == "around":
        return (arg + 0.02 * z).astype(dtype)
    rank = RANK.get(name, 2)
    base = shape[len(shape) - rank:]
    fan_in = int(np.prod(base[:arg]))
    return (z / np.sqrt(fan_in)).astype(dtype)


def make(model, seed: int):
    """All weights of ``model`` from ``seed``, on the default device."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    unknown = sorted({_leaf_name(p) for p, _ in flat} - set(RULES))
    if unknown:
        raise KeyError(f"no weight rule for leaves {unknown}")

    def build(key):
        keys = jax.random.split(key, len(flat))
        return treedef.unflatten(
            [_draw(_leaf_name(p), s, k) for (p, s), k in zip(flat, keys)])
    return jax.jit(build)(seed_key(seed))
