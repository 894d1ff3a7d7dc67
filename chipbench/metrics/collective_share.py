"""Share of the device's busy time spent in collective operations (the
all-reduces, all-gathers, reduce-scatters, collective-permutes and
all-to-alls the partitioner puts in a sharded step, with their async
``-start``/``-done`` halves), averaged over the chips; None where the
window holds no collective.

``share(view, scope)`` counts only the collectives whose ``op_name`` has
that scope (``tile_gather_share`` reads the gathers of the tile ops'
operands, ``kernels/ops.py``)."""
from typing import Optional

from chipbench import program_trace as P
from chipbench import trace as T

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(op: T.Op) -> bool:
    """By opcode; a generic ``async-start``/``-done`` by the name of the
    instruction it wraps (``%reduce-scatter-start.2 = ... async-start``)."""
    opcode = op.opcode
    if opcode.startswith("async-"):
        opcode = op.kernel
    for half in ("-start", "-update", "-done"):
        if opcode.endswith(half):
            opcode = opcode[:-len(half)]
    return opcode in COLLECTIVES


def share(view, scope: Optional[str] = None) -> Optional[float]:
    tr = view.trace if scope is None else P.for_view(view)
    if tr is None:
        return None
    ops = [o for o in T.kernel_ops(tr) if is_collective(o)
           and (scope is None or o.in_scope(scope))]
    busy = T.busy_s(tr) * len(tr.ops)
    if not ops or busy <= 0:
        return None
    return 100.0 * sum(o.seconds for o in ops) / busy


def read(view):
    return share(view)
