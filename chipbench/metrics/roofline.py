"""Shared arithmetic of kernel roofline shares.

For every device event of the chosen kernels in the traced window: the
least time the chip could take for that call, the largest of its
operations over the peak rate, its HBM bytes over the HBM bandwidth, and
its VMEM reads and writes over the VMEM read and write bandwidths
(counted from the call's shapes and memory spaces by
``chipbench/kernels/<kernel>.py``), summed and divided by the summed
device time of those events. Arrays the compiler keeps in VMEM (layout
``S(1)``) cost no HBM traffic; counting them at HBM bandwidth would
put shares above 100 %.
"""
from chipbench import bench
from chipbench import trace as T


def share(view, match):
    bound = spent = 0.0
    for op in T.kernel_ops(view.trace):
        if not match(op.kernel):
            continue
        cost = bench.kernel_cost(op.kernel)
        if cost is None:
            continue
        c = cost.cost(*op.shapes())
        p = view.peaks
        bound += max(c["flops"] / p["bf16_flops_per_s"],
                     c["hbm_bytes"] / p["hbm_bytes_per_s"],
                     c["vmem_read_bytes"] / p["vmem_read_bytes_per_s"],
                     c["vmem_write_bytes"] / p["vmem_write_bytes_per_s"])
        spent += op.seconds
    if spent <= 0:
        return None
    return 100.0 * bound / spent


def kernel_seconds(view, match) -> float:
    return sum(op.seconds for op in T.kernel_ops(view.trace)
               if match(op.kernel))
