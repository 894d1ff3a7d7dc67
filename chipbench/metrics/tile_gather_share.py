"""Share of the device's busy time spent gathering the tile ops'
operands under a mesh: the collectives in the ``tile_gather`` scope
(``kernels/ops.py``), averaged over the chips."""
from chipbench import bench

_collective = bench.metric_reader("collective_share")


def read(view):
    return _collective.share(view, "tile_gather")
