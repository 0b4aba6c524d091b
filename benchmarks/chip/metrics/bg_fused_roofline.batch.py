"""Kernel: least time of one frame over the Mosaic kernels' device time per frame, in %."""
from readings import roofline_pct


def read(ctx):
    return roofline_pct(ctx)
