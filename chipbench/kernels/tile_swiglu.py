"""The saturated ``swiglu`` tile kernel: 5 operations an element (a*sigmoid(a)*b: exp, add, divide, two multiplies)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 5


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
