"""Operation counts of the RWKV-6 family (``reference/rwkv6.py``): the
time-mix with its LoRAs, the channel-mix, and the WKV recurrence."""
from bench import flops


def layer_matmul_params(c: dict, layer: int) -> int:
    """Weights one token multiplies through in one layer: r, k, v, g, o,
    the token-shift LoRA (5 mixes) and the decay LoRA; the channel-mix's
    key, value and receptance."""
    d = c["d_model"]
    mix, decay = c["rwkv_mix_lora_rank"], c["rwkv_decay_lora_rank"]
    tmix = 5 * d * d + 2 * 5 * mix * d + 2 * decay * d
    cmix = 2 * d * c["d_ff"] + d * d
    return tmix + cmix


def layer_token_flops(c: dict, layer: int) -> int:
    """The WKV recurrence of one token."""
    return flops.wkv_token_flops(c)
