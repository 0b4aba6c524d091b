"""Small readings that several metric readers share."""
from __future__ import annotations

import math


def answered(ctx) -> list:
    """Outcomes whose answer reached host memory inside the window."""
    return [oc for oc in ctx["outcomes"]
            if oc.error is None and oc.done <= ctx["seconds"]]


def latency_ms(ctx, q: float):
    """Nearest-rank ``q`` quantile of due-to-host latency over every frame
    due in the window; a frame that failed or never came counts as
    missing every limit (infinite). None when that quantile is infinite."""
    lat = sorted((oc.done - oc.due) * 1e3 if oc.error is None else math.inf
                 for oc in ctx["outcomes"])
    if not lat:
        return None
    value = lat[max(0, math.ceil(q * len(lat)) - 1)]
    return value if math.isfinite(value) else None


def per_dispatch(ctx):
    e = ctx["engine"]
    return e["completed"] / e["dispatches"] if e["dispatches"] else None


def idle_share_pct(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def busy_ms_per_frame(ctx):
    t = ctx.get("trace")
    n = len(answered(ctx))
    if not t or not n or t["busy_s"] <= 0:
        return None
    return 1e3 * t["busy_s"] * ctx["n_devices"] / n


def roofline_pct(ctx):
    """(share of the kernel's least time, bound): the least time of one
    frame (``workcount``) over the kernel's device time per frame."""
    from workcount import least_time_s

    t = ctx.get("trace")
    n = len(answered(ctx))
    if not t or not n or not t["kernel_events"] or t["kernel_s"] <= 0:
        return None
    least, bound = least_time_s(ctx["config"], ctx["device_kind"])
    return 100.0 * least / (t["kernel_s"] / n), bound
