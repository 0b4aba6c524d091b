"""Client: mean share of the fetch pool's threads busy bringing answers to
host memory in the window. Near 100% the client, not the system, would cap
the closed loop."""


def read(ctx):
    return 100.0 * ctx["fetch_busy"]
