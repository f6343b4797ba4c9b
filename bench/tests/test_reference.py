"""Each reference implements the model the program serves: at small
widths on the CPU, in float32, its logits match the program's prefill
logits over the whole sequence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import configs, reference, weights
from bench.tests.tiny import SIZES, config
from repro.models import build_model


@pytest.mark.parametrize("name", sorted(SIZES))
def test_reference_matches_program_prefill_logits(name):
    data = config(name, compute_dtype="float32")
    model = build_model(configs.model_config(data))
    w = weights.make(model, 2 ** 31 + 77)
    toks = np.random.default_rng(0).integers(0, data["vocab"], 40)
    toks = jnp.asarray(toks, jnp.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = model.forward(w, {"tokens": toks[None]})
        ref = reference.load(data["reference"]).logits(
            w, data, toks, jnp.arange(40), reference.dot_f32)
    prog = np.asarray(prog[0, :, :data["vocab"]], np.float32)
    ref = np.asarray(ref)
    # float32 on both sides: rounding only, far below a logit's spread
    assert np.abs(prog - ref).max() <= 1e-4 * np.abs(ref).max()


@pytest.mark.parametrize("name", sorted(SIZES))
def test_float8_control_departs_from_the_reference(name):
    data = config(name)
    model = build_model(configs.model_config(data))
    w = weights.make(model, 5)
    toks = jnp.asarray(np.arange(32) % data["vocab"], jnp.int32)
    mod = reference.load(data["reference"])
    ref = np.asarray(mod.logits(w, data, toks, jnp.arange(32),
                                reference.dot_f32))
    ctl = np.asarray(mod.logits(w, data, toks, jnp.arange(32),
                                reference.dot_fp8))
    assert np.abs(ctl - ref).max() > 1e-2 * np.abs(ref).max()


def test_weights_repeat_from_the_seed():
    data = config("rwkv6-1.6b")
    model = build_model(configs.model_config(data))
    a = weights.make(model, 2 ** 31 + 3)
    b = weights.make(model, 2 ** 31 + 3)
    c = weights.make(model, 3)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not np.array_equal(a["lm_head"], c["lm_head"])
