"""Model-level parity: the Pallas kernel path (interpret mode on CPU)
must reproduce the XLA-path forward/prefill/decode for each kernel-
backed family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.common import kernel_path
from repro.models import build_model

B, S = 2, 32


def _pair(arch, rng_key):
    cfg = get_config(arch, reduced=True)
    m = build_model(cfg)
    params = m.init(rng_key)
    toks = jax.random.randint(rng_key, (B, S), 0, cfg.vocab)
    return cfg, m, params, toks


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "internlm2-1.8b",
                                  "rwkv6-7b", "recurrentgemma-2b",
                                  "mixtral-8x7b"])
def test_forward_parity(arch, rng_key):
    cfg, m, params, toks = _pair(arch, rng_key)
    with kernel_path(False):
        y_x, _ = m.forward(params, {"tokens": toks})
    with kernel_path(True):
        y_p, _ = m.forward(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(y_p, np.float32),
                               np.asarray(y_x, np.float32),
                               atol=5e-2)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mixtral-8x7b"])
def test_decode_parity(arch, rng_key):
    cfg, m, params, toks = _pair(arch, rng_key)
    with kernel_path(False):
        _, caches_x = m.prefill(params, {"tokens": toks[:, :S - 2]},
                                capacity=S)
    with kernel_path(True):
        _, caches_p = m.prefill(params, {"tokens": toks[:, :S - 2]},
                                capacity=S)
    for t in range(S - 2, S):
        with kernel_path(False):
            lx, caches_x = m.decode(params, caches_x, toks[:, t:t + 1],
                                    jnp.int32(t))
        with kernel_path(True):
            lp, caches_p = m.decode(params, caches_p, toks[:, t:t + 1],
                                    jnp.int32(t))
        np.testing.assert_allclose(np.asarray(lp, np.float32),
                                   np.asarray(lx, np.float32), atol=5e-2)
