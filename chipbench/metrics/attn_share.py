"""Share of the device's busy time spent in the program's attention
layers: kernel ops whose ``op_name`` has an ``attn`` scope
(``models/layers.py``), a fusion counted by the scope XLA gave it."""
from chipbench import program_trace as P


def read(view):
    tr = P.for_view(view)
    return None if tr is None else P.scope_share(tr, "attn")
