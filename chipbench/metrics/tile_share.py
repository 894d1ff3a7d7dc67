"""Share of the device's busy time spent in the tile-op kernels."""
from chipbench import bench
from chipbench import trace as T

_roof = bench.metric_reader("roofline")


def read(view):
    busy = T.busy_s(view.trace)
    tile = _roof.kernel_seconds(view, lambda k: k.startswith("tile_"))
    if busy <= 0 or tile <= 0:
        return None
    return 100.0 * tile / busy
