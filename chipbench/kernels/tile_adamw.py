"""The saturated ``adamw`` tile kernel: 12 operations an element (two moment updates, bias corrections, sqrt, divide, decay, step)."""
from chipbench.kernels import elementwise

FLOPS_PER_ELEMENT = 12


def cost(operands, results):
    return elementwise.cost(operands, results, FLOPS_PER_ELEMENT)
