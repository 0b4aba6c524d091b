"""95th percentile due-to-host latency over every frame due in the window (end to end)."""
from readings import latency_ms


def read(ctx):
    return latency_ms(ctx, 0.95)
