"""A model family goes into the benchmark as new files only: a
configuration file with nested groups, a family module with weight
rules of its own, an operation-count module, all in a benchmark
directory of the test's own. And the families already in the benchmark
draw the same weights and count the same work as before."""
import dataclasses
import hashlib
import json
import textwrap

import jax
import numpy as np
import pytest

from bench import configs, flops, reference, weights
from bench.tests.tiny import config
from repro.configs import MoEConfig, get_config
from repro.models import build_model

#: the program's registered tiny mixtral-8x7b, as a configuration file;
#: ``num_local_experts`` stands for a key of a published configuration
#: that the program takes under another name
MIXTRAL = {
    "name": "mixtral-tiny", "source": "https://arxiv.org/abs/2401.04088",
    "arch": "mixtral-8x7b", "reference": "moe", "family": "moe",
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
    "d_head": 16, "d_ff": 96, "vocab": 512, "block_pattern": ["swa"],
    "window": 16, "max_seq_len": 256, "rope_theta": 10000.0,
    "num_local_experts": 4,
    "moe": {"n_experts": 4, "top_k": 2, "d_expert": 96,
            "capacity_factor": 2.0},
    "reduced": ["moe.n_experts"],
}

FAMILY = """
REFERENCE_ONLY = ("num_local_experts",)
WEIGHT_RULES = {"router": ("fan_in", 1)}
"""

COUNTS = """
def layer_matmul_params(c, layer):
    d, m = c["d_model"], c["moe"]
    hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["d_head"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    return attn + m["top_k"] * 3 * d * m["d_expert"] + d * m["n_experts"]
"""


def write_bench(root, family=FAMILY, cfg=MIXTRAL):
    """A benchmark directory that holds one family, as files only."""
    for kind, name, text in (("configs", f"{cfg['name']}.json",
                              json.dumps(cfg)),
                             ("reference", "moe.py", family),
                             ("counts", "moe.py", COUNTS)):
        (root / kind).mkdir(exist_ok=True)
        (root / kind / name).write_text(textwrap.dedent(text))
    return configs.load(cfg["name"], root)


def test_nested_groups_load_into_the_programs_dataclasses(tmp_path):
    cfg = configs.model_config(write_bench(tmp_path))
    tiny = get_config("mixtral-8x7b", reduced=True)
    assert isinstance(cfg.moe, MoEConfig) and cfg.block_pattern == ("swa",)
    hash(cfg)
    assert dataclasses.replace(cfg, sharding=tiny.sharding,
                               source=tiny.source) == tiny


def test_a_key_the_program_has_no_size_for_is_refused(tmp_path):
    data = write_bench(tmp_path, cfg={**MIXTRAL, "moe": {
        **MIXTRAL["moe"], "n_expertz": 4}})
    with pytest.raises(KeyError, match="n_expertz"):
        configs.model_config(data)


def test_reduced_names_only_keys_the_file_holds(tmp_path):
    with pytest.raises(KeyError, match="moe.n_routed"):
        write_bench(tmp_path, cfg={**MIXTRAL, "reduced": ["moe.n_routed"]})


def test_the_family_rules_draw_the_router(tmp_path):
    data = write_bench(tmp_path)
    model = build_model(configs.model_config(data))
    fam = reference.load("moe", tmp_path)
    w = weights.make(model, 2 ** 31 + 9, rules=weights.joined(fam))
    router = np.asarray(w["segments"][0][0]["ffn"]["router"])
    assert router.shape == (2, 64, 4)
    # fan_in over d_model: a standard deviation of 1/8
    assert 0.1 < router.std() < 0.15


@pytest.mark.parametrize("rules,error", [
    ('WEIGHT_RULES = {"router": ("fan_in", 1), "wq": ("normal", 1.0)}',
     ValueError),
    ('WEIGHT_RULES = {"router": ("fan_in", 1)}\nWEIGHT_RANK = {"w_up": 3}',
     ValueError),
    ("", KeyError)], ids=["shared_rule", "shared_rank", "no_rule"])
def test_a_clashing_or_missing_rule_is_an_error(tmp_path, rules, error):
    family = 'REFERENCE_ONLY = ("num_local_experts",)\n' + rules
    data = write_bench(tmp_path, family=family)
    model = build_model(configs.model_config(data))
    with pytest.raises(error):
        weights.make(model, 1, rules=weights.joined(
            reference.load("moe", tmp_path)))


def test_the_family_count_module_gives_the_step_flops(tmp_path):
    c = write_bench(tmp_path)
    # q, o 64·64, k, v 64·32; two experts of three 64·96; router 64·4
    per_layer = 2 * 64 * 64 + 2 * 64 * 32 + 2 * 3 * 64 * 96 + 64 * 4
    assert flops.layer_matmul_params(c) == per_layer
    # window 16: contexts 10 and 30 read 10 and 16 keys in each layer
    assert flops.decode_flops(c, [10, 30]) == (
        2 * 2 * (2 * per_layer + 64 * 512) + 2 * 4 * 4 * 16 * (10 + 16))
    # 4 tokens from position 14 see 15, 16, 17, 18 keys: 15, 16, 16, 16
    assert flops.chunk_flops(c, 4, 14) == (
        2 * 4 * 2 * per_layer + 2 * 64 * 512
        + 2 * 4 * 4 * 16 * (15 + 16 + 16 + 16))


def test_paged_attention_of_window_and_full_layers_by_hand(tmp_path):
    c = write_bench(tmp_path, cfg={
        **MIXTRAL, "n_layers": 8, "window": 16,
        "block_pattern": ["swa", "swa", "swa", "attn"]})
    assert flops.attn_windows(c) == [16, 16, 16, 0] * 2
    lengths = [10, 30]
    got = np.sum([flops.paged_attn_cost(c, lengths, w)
                  for w in flops.attn_windows(c)], axis=0)

    def layer(keys):
        # 4 heads of 16 over the keys; K and V of 2 KV heads in bf16, the
        # queries read and the outputs written
        return np.array([4 * 4 * 16 * keys,
                         2 * 2 * 16 * 2 * keys + 2 * 2 * 4 * 16 * 2])
    want = 6 * layer(10 + 16) + 2 * layer(10 + 30)
    assert got.tolist() == want.tolist()


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


#: the weights every cell of these configurations runs on; a change to
#: how a leaf is drawn moves every reading of those cells
DIGESTS = {
    ("internlm2-1.8b", 2 ** 31 + 5):
        "29c65ce0d7bb2be937bfda68b4d707d245b5aa28a6ee34fc19f61846ce2ed142",
    ("internlm2-1.8b", 7):
        "9ae5e9e443f9821bf42bb2194eaa30663d15ad32a8fcadef725d080279395427",
    ("rwkv6-1.6b", 2 ** 31 + 5):
        "781dd7a8d432e48abb7f182953012becf948b137b96d0a722fb807c6591b6c76",
    ("rwkv6-1.6b", 7):
        "b1cab980dffe46b1d613ca31413697f4b4a2a8ee25c619b3db0c8ade6480b444",
}


@pytest.mark.parametrize("name,seed", sorted(DIGESTS))
def test_seeded_weights_are_pinned(name, seed):
    data = config(name)
    model = build_model(configs.model_config(data))
    rules = weights.joined(reference.load(data["reference"]))
    assert digest(weights.make(model, seed, rules=rules)) == DIGESTS[
        name, seed]
