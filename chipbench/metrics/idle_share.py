"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), averaged over the chips."""
from chipbench import trace as T


def read(view):
    tr = view.trace
    if not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - T.busy_s(tr) / tr.window_s)
