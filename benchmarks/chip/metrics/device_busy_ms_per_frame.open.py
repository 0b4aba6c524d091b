"""Device: busy time in the traced window per frame answered in it."""
from readings import busy_ms_per_frame


def read(ctx):
    return busy_ms_per_frame(ctx)
