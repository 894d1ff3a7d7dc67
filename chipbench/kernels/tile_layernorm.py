"""The saturated ``layernorm`` tile kernel: 7 operations an element (the mean, x-mu, (x-mu)^2, its sum, *rsqrt, *g, +b)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 7


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
