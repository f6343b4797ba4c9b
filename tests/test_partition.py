"""Sharding-rule tests: every proposed spec divides its dimension on the
production mesh shape; scan-segment handling; cache fallbacks (split-KV,
B=1 sequence-parallel)."""
import numpy as np
import pytest

import jax
from repro.configs import get_config, list_archs
from repro.models import build_model
from repro.parallel.partition import (_sanitize, batch_pspecs, cache_pspecs,
                                      param_pspecs)


class FakeMesh:
    """Duck-typed mesh: shape mapping + axis names (no devices needed)."""

    def __init__(self, sizes):
        self.shape = dict(sizes)
        self.axis_names = tuple(sizes)

    @property
    def devices(self):
        return np.zeros([self.shape[a] for a in self.axis_names])


POD_MESH = FakeMesh({"data": 16, "model": 16})
MULTI_MESH = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _check_divisible(specs, tree, mesh):
    sizes = {a: mesh.shape[a] for a in mesh.axis_names}
    from jax.sharding import PartitionSpec
    leaves_s = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    leaves_t = jax.tree.leaves(tree)
    assert len(leaves_s) == len(leaves_t)
    for spec, leaf in zip(leaves_s, leaves_t):
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = int(np.prod([sizes[a] for a in axes]))
            assert leaf.shape[d] % total == 0, (spec, leaf.shape)


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("mesh", [POD_MESH, MULTI_MESH],
                         ids=["pod", "multi"])
def test_param_specs_divide_production_mesh(arch, mesh):
    cfg = get_config(arch)          # FULL config, real dims
    model = build_model(cfg)
    abs_p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_pspecs(cfg, abs_p, mesh)
    _check_divisible(specs, abs_p, mesh)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mixtral-8x7b",
                                  "rwkv6-7b", "whisper-medium"])
def test_cache_specs_divide(arch):
    cfg = get_config(arch)
    model = build_model(cfg)
    from functools import partial
    cache_abs = jax.eval_shape(partial(model.init_cache, 128, 32768))
    specs = cache_pspecs(cfg, cache_abs, POD_MESH, 128)
    _check_divisible(specs, cache_abs, POD_MESH)


def test_cache_split_kv_fallback():
    """kv=8 heads cannot shard a 16-way axis → cache seq dim shards."""
    cfg = get_config("internlm2-1.8b")
    model = build_model(cfg)
    from functools import partial
    cache_abs = jax.eval_shape(partial(model.init_cache, 128, 32768))
    specs = cache_pspecs(cfg, cache_abs, POD_MESH, 128)
    from jax.sharding import PartitionSpec
    flat = jax.tree.leaves(specs,
                           is_leaf=lambda x: isinstance(x, PartitionSpec))
    kv_specs = [s for s in flat if len(s) == 5]     # scanned (n,B,C,H,hd)
    assert any(s[2] == "model" for s in kv_specs), kv_specs


def test_b1_long_context_sequence_parallel():
    cfg = get_config("mixtral-8x7b")
    model = build_model(cfg)
    from functools import partial
    cache_abs = jax.eval_shape(partial(model.init_cache, 1, 524288))
    specs = cache_pspecs(cfg, cache_abs, POD_MESH, 1)
    from jax.sharding import PartitionSpec
    flat = jax.tree.leaves(specs,
                           is_leaf=lambda x: isinstance(x, PartitionSpec))
    assert any(("data", "model") in tuple(s) for s in flat), flat[:4]


def test_batch_pspec_replicates_indivisible():
    import jax.numpy as jnp
    cfg = get_config("mixtral-8x7b")
    batch = {"token": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
    specs = batch_pspecs(cfg, batch, POD_MESH)
    assert tuple(specs["token"]) == (None, None)


def test_sanitize_drops_non_dividing_axes():
    sizes = {"data": 16, "model": 16}
    assert _sanitize(("model", None), (10, 4), sizes) == (None, None)
    assert _sanitize(("model", "data"), (32, 32), sizes) == \
        ("model", "data")
    assert _sanitize((("data", "model"), None), (512, 4), sizes)[0] == \
        ("data", "model")
    assert _sanitize((("data", "model"), None), (100, 4), sizes) == \
        (None, None)


def test_train_step_on_local_mesh():
    """Jitted train steps on a ``make_local_mesh`` mesh. The second step
    takes the first one's sharded outputs, and its embedding gather over
    the model-sharded table only resolves on Auto mesh axes: Explicit
    axes (``jax.make_mesh``'s default) raise ShardingTypeError there."""
    import jax.numpy as jnp

    from repro import optim
    from repro.configs.base import ShapeCell
    from repro.data import pipeline_for
    from repro.launch.mesh import make_local_mesh
    from repro.parallel import build_train

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    cell = ShapeCell("t", 16, 2, "train")
    mesh = make_local_mesh((1, 1))
    opt_cfg = optim.OptConfig(state_dtype=cfg.opt_dtype)
    step, _ = build_train(cfg, mesh, cell, opt_cfg)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v)
             for k, v in pipeline_for(cfg, cell, seed=0).batch(0).items()}
    state = (params, optim.init(opt_cfg, params))
    for _ in range(2):
        *state, metrics = step(*state, batch)
        assert np.isfinite(float(metrics["loss"]))
