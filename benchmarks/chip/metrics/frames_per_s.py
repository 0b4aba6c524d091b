"""Frames answered in the window, over the window's length (end to end)."""
from readings import answered


def read(ctx):
    return len(answered(ctx)) / ctx["seconds"]
