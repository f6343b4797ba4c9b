"""Kernels (kernels/decode_attention): share of the roofline reached by
``fused_paged_decode_attention`` in the traced steps: the least time of
its calls (one per attention layer per decode step; FLOPs and bytes of
the valid keys and values, bf16 pools) over its device time in the
trace."""
from bench import flops
from bench.metrics import kernel_roofline

PATTERN = r"^%fused_paged_decode_attention"


def read(run):
    c = run.cfg

    def cost(step):
        if step.lengths is None or not len(step.lengths):
            return []
        return [flops.paged_attn_cost(c, step.lengths)] * c["n_layers"]
    return kernel_roofline(run, PATTERN, cost)
