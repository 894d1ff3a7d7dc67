"""Share of the traced window in which the device was idle while the
serving loop read tokens back to the host (inside a
``repro.serve.readback`` span of ``launch/serve.py``)."""
from chipbench import program_trace as P


def read(view):
    tr = P.for_view(view)
    return None if tr is None else P.idle_share_inside(
        tr, "repro.serve.readback")
