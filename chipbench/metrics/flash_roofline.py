"""Roofline share of the flash-attention forward kernel."""
from chipbench import bench

_roof = bench.metric_reader("roofline")


def read(view):
    return _roof.share(view, lambda k: k == "flash_attention")
