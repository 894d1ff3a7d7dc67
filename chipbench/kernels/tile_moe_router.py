"""The saturated ``moe_router`` tile kernel: 5 operations an element (the softmax)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 5


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
