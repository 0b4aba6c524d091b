"""Process start to the first frame of the window (end to end)."""


def read(ctx):
    return ctx["setup_s"]
