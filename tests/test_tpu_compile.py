"""Compile-only checks for the TPU v5e, without a chip.

The TPU compiler is installed here and compiles for a described ``v5e:2x2``
topology. It refuses what interpret mode accepts: block shapes whose last
two dims are neither (8, 128)-aligned nor whole, and kernels that need more
VMEM than a core has. Every serving kernel, the full-width fused decode step
and one prefill chunk of ``qwen1.5-0.5b`` compile here, with the kernels
lowered as Mosaic custom calls (``tpu_custom_call``), never as plain HLO.

The topology is described inside a fixture and nowhere else: only one
process may load the TPU library, and a test worker that loads it keeps it
until it exits. Keep every such test in this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import common
from repro.kernels.common import kernel_path
from repro.models import build_model

B, PAGE, PAGES, NB = 8, 16, 1024, 128     # 8 slots, 2,048 tokens each


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs or
    ShapeDtypeStruct trees; returns the compiled program's HLO text."""
    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
    args = [jax.tree.map(place, s) if not isinstance(s, tuple)
            else place(jax.ShapeDtypeStruct(*s)) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _qwen():
    cfg = get_config("qwen1.5-0.5b")
    return cfg, cfg.n_heads, cfg.n_kv_heads, cfg.d_head


def _paged(kind):
    from repro.kernels.decode_attention.decode_attention import (
        fused_paged_decode_attention, paged_decode_attention)
    _, hq, hkv, hd = _qwen()
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = ((PAGES, PAGE, hkv, hd), bf)
    tail = (((B,), i32), ((B, NB), i32))
    if kind == "fused":
        kv = ((B, hkv, 1, hd), bf)
        return (functools.partial(fused_paged_decode_attention,
                                  interpret=False),
                ((B, hq, 1, hd), bf), kv, kv, pool, pool) + tail
    return (functools.partial(paged_decode_attention, interpret=False),
            ((B, hq, 1, hd), bf), pool, pool) + tail


def _internlm2_fused(b, nb):
    """The benchmark's InternLM2 decode attention: bf16 pools of 1,536
    pages × 16 × 8 × 128, ``b`` slots of ``nb`` blocks (chat 12 × 128,
    docs 3 × 512); its double-buffered page blocks must fit in VMEM."""
    from repro.kernels.decode_attention.decode_attention import (
        fused_paged_decode_attention)
    cfg = get_config("internlm2-1.8b")
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    bf, i32 = jnp.bfloat16, jnp.int32
    pool, kv = ((1536, PAGE, hkv, hd), bf), ((b, hkv, 1, hd), bf)
    return (functools.partial(fused_paged_decode_attention, interpret=False),
            ((b, hq, 1, hd), bf), kv, kv, pool, pool, ((b,), i32),
            ((b, nb), i32))


def _sample():
    from repro.kernels.decode_attention.decode_attention import (
        sample_tokens)
    cfg, *_ = _qwen()
    f32 = jnp.float32
    return (functools.partial(sample_tokens, interpret=False),
            ((B, cfg.vocab), f32), ((B,), f32), ((B, cfg.vocab), f32))


def _flash():
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention)
    _, hq, hkv, hd = _qwen()
    S, bf = 512, jnp.bfloat16
    return (functools.partial(flash_attention, causal=True, interpret=False),
            ((1, hq, S, hd), bf), ((1, hkv, S, hd), bf),
            ((1, hkv, S, hd), bf))


def _rglru():
    from repro.kernels.rglru_scan.rglru_scan import rglru_scan
    d, f32 = get_config("recurrentgemma-2b").d_model, jnp.float32
    return (functools.partial(rglru_scan, interpret=False),
            ((B, 512, d), f32), ((B, 512, d), f32), ((B, d), f32))


def _rwkv():
    from repro.kernels.rwkv6_wkv.rwkv6_wkv import rwkv6_wkv
    cfg, f32 = get_config("rwkv6-7b"), jnp.float32
    H, K = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    x = ((2, H, 256, K), f32)
    return (functools.partial(rwkv6_wkv, interpret=False),
            x, x, x, x, ((H, K), f32), ((2, H, K, K), f32))


KERNELS = {
    "paged_decode_attention": lambda: _paged("paged"),
    "fused_paged_decode_attention": lambda: _paged("fused"),
    "fused_paged_decode_attention.chat": lambda: _internlm2_fused(12, 128),
    "fused_paged_decode_attention.docs": lambda: _internlm2_fused(3, 512),
    "sample_tokens": _sample,
    "flash_attention": _flash,
    "rglru_scan": _rglru,
    "rwkv6_wkv": _rwkv,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, *shapes = KERNELS[name]()
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)


@pytest.fixture()
def on_tpu_path(monkeypatch):
    """Trace the model as it is traced on the chip: Pallas kernels, not
    interpreted (the CPU backend would otherwise interpret them)."""
    monkeypatch.setattr(common, "use_interpret", lambda: False)
    with kernel_path(True):
        yield


def _serving_shapes():
    cfg, *_ = _qwen()
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda: model.init_paged_state(B, PAGES, PAGE))
    return model, params, state


def test_fused_decode_step_compiles_for_v5e(one_chip, on_tpu_path):
    """The chunked path's decode step at full width: paged attention with
    the new token in-register, then on-device sampling."""
    model, params, state = _serving_shapes()
    i32 = jnp.int32
    text = _compile(model.decode_paged_fused, one_chip, params, state,
                    ((B, 1), i32), ((B,), i32), ((B, NB), i32),
                    ((B,), jnp.float32), ((), i32))
    assert text.count("tpu_custom_call") >= 2       # attention + sampler


def test_prefill_chunk_compiles_for_v5e(one_chip, on_tpu_path):
    model, params, state = _serving_shapes()
    i32 = jnp.int32
    _compile(model.prefill_chunk_paged, one_chip, params, state,
             ((1, 64), i32), ((), i32), ((NB,), i32), ((), i32))


def test_sharded_decode_program_compiles_for_v5e(topo, on_tpu_path):
    """The VMM's multi-chip path: a full-width decode program for a (1, 2)
    slice of the pod. Its kernel runs per head shard (a Mosaic kernel is
    never auto-partitioned) and the program stays on the slice's chips."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs.base import ShapeCell
    from repro.parallel.steps import build_decode

    devs = topo.devices[2:4]
    mesh = Mesh(np.array(devs).reshape(1, 2), ("data", "model"))
    step, args = build_decode(get_config("qwen1.5-0.5b"), mesh,
                              ShapeCell("d", 64, 4, "decode"))
    compiled = step.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    for s in jax.tree.leaves(compiled.output_shardings):
        assert set(s.device_set) == set(devs)
