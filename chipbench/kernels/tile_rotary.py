"""The saturated ``rotary`` tile kernel: 3 operations an element (q*cos + rotate_half(q)*sin)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 3


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
