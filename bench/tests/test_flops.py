"""Operation and byte counts from shapes, against counts by hand."""
import pytest

from bench import configs, flops

DENSE = configs.load("internlm2-1.8b")
RWKV = configs.load("rwkv6-1.6b")


def test_dense_layer_params_match_the_published_total():
    per_layer = flops.layer_matmul_params(DENSE)
    # q and o: 2048·2048; k and v: 2048·1024; SwiGLU: 3·2048·8192
    assert per_layer == 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    total = 24 * per_layer + 2 * 92544 * 2048
    assert total == pytest.approx(1.889e9, rel=1e-3)


def test_rwkv_layer_params_match_the_published_total():
    d, f = 2048, 7168
    per_layer = flops.layer_matmul_params(RWKV)
    # r, k, v, g, o; token-shift LoRA rank 32 (5 mixes), decay LoRA
    # rank 64; channel-mix key, value and receptance
    assert per_layer == (
        5 * d * d + 2 * 5 * 32 * d + 2 * 64 * d + 2 * d * f + d * d)
    total = 24 * per_layer + 2 * 65536 * d
    assert total == pytest.approx(1.6e9, rel=1e-2)


def test_decode_flops_by_hand():
    lengths = [10, 30]
    matmul = 2 * 2 * (24 * flops.layer_matmul_params(DENSE) + 2048 * 92544)
    attn = 24 * 4 * 16 * 128 * 40
    assert flops.decode_flops(DENSE, lengths) == matmul + attn


def test_chunk_flops_by_hand():
    # 4 tokens from position 6: they see 7, 8, 9 and 10 keys
    f = flops.chunk_flops(DENSE, 4, 6)
    want = (2 * 4 * 24 * flops.layer_matmul_params(DENSE) + 2 * 2048 * 92544
            + 24 * 4 * 16 * 128 * (7 + 8 + 9 + 10))
    assert f == want


def test_paged_attention_cost_by_hand():
    f, b = flops.paged_attn_cost(DENSE, [100, 28])
    assert f == 4 * 16 * 128 * 128
    # K and V of 128 tokens, 8 heads of 128, bf16; q in and o out
    assert b == 2 * 8 * 128 * 2 * 128 + 2 * 2 * 16 * 128 * 2


def test_wkv_cost_by_hand():
    f, b = flops.wkv_cost(RWKV, 512)
    assert f == 512 * 32 * (5 * 64 * 64 + 4 * 64)
    assert b == 4 * (5 * 512 * 32 * 64 + 2 * 32 * 64 * 64 + 32 * 64)


def test_roofline_takes_the_slower_bound():
    assert flops.roofline_s(197e12, 1.0, 197e12, 819e9) == pytest.approx(1.0)
    assert flops.roofline_s(1.0, 819e9, 197e12, 819e9) == pytest.approx(1.0)
