"""Cost of a row-tiled elementwise kernel: it reads every operand and
writes every result once; ``flops_per_element`` counts the arithmetic per
element of its first operand. Bytes are split by where each array lives:
HBM, or VMEM where the compiler placed it there (layout ``S(1)``)."""
from chipbench import trace as T


def moved(operands, results):
    """Bytes a kernel moves: HBM both ways, VMEM reads, VMEM writes."""
    return {"hbm_bytes": float(T.nbytes(operands, 0) + T.nbytes(results, 0)),
            "vmem_read_bytes": float(T.nbytes(operands, 1)),
            "vmem_write_bytes": float(T.nbytes(results, 1))}


def cost(operands, results, flops_per_element):
    n = 1
    for d in (operands[0][1] if operands else ()):
        n *= d
    return dict(moved(operands, results), flops=float(flops_per_element * n))
