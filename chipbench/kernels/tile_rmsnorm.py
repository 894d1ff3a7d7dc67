"""The saturated ``rmsnorm`` tile kernel: 4 operations an element (x*x, the row sum, x*rsqrt, *g)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 4


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
