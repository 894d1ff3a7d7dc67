"""The saturated ``gelu`` tile kernel: 8 operations an element (the tanh form: a^3, two multiply-adds, tanh, 1+, two multiplies)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 8


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
