"""Share of the device's busy time spent in the tile-op kernels,
averaged over the chips. ``tile_share.py`` divides the kernels' time
summed over every chip by one chip's busy time, which holds on one chip
and reads the chip count times too high on several."""
from chipbench import bench
from chipbench import trace as T

_roof = bench.metric_reader("roofline")


def read(view):
    busy = T.busy_s(view.trace) * len(view.trace.ops)
    tile = _roof.kernel_seconds(view, lambda k: k.startswith("tile_"))
    if busy <= 0 or tile <= 0:
        return None
    return 100.0 * tile / busy
