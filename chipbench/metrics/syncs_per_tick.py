"""Device-to-host reads the serving loop makes a tick: the ``syncs`` of
the ``repro.serve.readback`` spans that start in the traced window, over
their number."""
from chipbench import program_trace as P


def read(view):
    tr = P.for_view(view)
    if tr is None:
        return None
    spans = tr.spans_in_window("repro.serve.readback")
    if not spans:
        return None
    return sum(int(s.stats["syncs"]) for s in spans) / len(spans)
