"""Engine: frames completed per dispatch in the window (EngineStats deltas)."""
from readings import per_dispatch


def read(ctx):
    return per_dispatch(ctx)
