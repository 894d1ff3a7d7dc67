"""The saturated ``residual_scale`` tile kernel: 2 operations an element (x + alpha*y)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 2


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
