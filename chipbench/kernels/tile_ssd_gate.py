"""The saturated ``ssd_gate`` tile kernel: 6 operations an element (softplus and exp(dt*-exp(a)))."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 6


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
