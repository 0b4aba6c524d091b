"""Plan: executables built or loaded from the cache inside the window."""


def read(ctx):
    return ctx["compiles_in_window"]
