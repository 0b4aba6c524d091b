"""Device: 1 - union of device-op intervals / traced window, in %."""
from readings import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx)
