"""The flash-attention forward kernel: q (B*H, S, D), k and v
(B, KH, S, D) in; o (B*H, S, D) and the per-row log-sum-exp out.

Bytes: every operand read and every result written once, split by
memory space as for the tile kernels. Operations: the
two products of causal attention, 4 * B*H * D * S(S+1)/2. The call's
shapes do not say whether it was causal; counting causal work for every
call keeps the share a lower bound for non-causal calls where operations
bind, and never above what the call did.
"""
from chipbench.kernels import elementwise


def cost(operands, results):
    bh, s, d = operands[0][1]
    return dict(elementwise.moved(operands, results),
                flops=4.0 * bh * d * s * (s + 1) / 2)
