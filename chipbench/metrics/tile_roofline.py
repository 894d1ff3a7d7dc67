"""Roofline share of the saturated tile-op kernels (``tile_<op>``)."""
from chipbench import bench

_roof = bench.metric_reader("roofline")


def read(view):
    return _roof.share(view, lambda k: k.startswith("tile_"))
