"""Kernels (kernels/rwkv6_wkv): share of the roofline reached by the
``rwkv6_wkv`` kernel in the traced steps' prefill chunks (one call per
``rwkv`` layer per chunk; FLOPs and bytes of the recurrence, float32)."""
from bench import flops
from bench.metrics import kernel_roofline

PATTERN = r"^%rwkv6_wkv"


def read(run):
    c = run.cfg
    layers = flops.layer_kinds(c).count("rwkv")

    def cost(step):
        return [flops.wkv_cost(c, length)
                for length, _ in step.chunks] * layers
    return kernel_roofline(run, PATTERN, cost)
