"""The saturated ``rmsnorm_gated`` tile kernel: 7 operations an element (z*sigmoid(z), *x, then the rmsnorm)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 7


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
