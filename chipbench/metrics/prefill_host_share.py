"""Share of the traced window in which the device was idle while the
serving loop was inside a prefill (a ``repro.serve.prefill`` span of
``launch/serve.py``): the prefill's host work."""
from chipbench import program_trace as P


def read(view):
    tr = P.for_view(view)
    return None if tr is None else P.idle_share_inside(
        tr, "repro.serve.prefill")
