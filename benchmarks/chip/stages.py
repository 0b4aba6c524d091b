"""Per-frame engine stages, read from the stage stamps that the engine keeps
for every finished request (``AsyncFrameEngine.timeline()``):

  queue     t_taken - t_submit      waiting for the dispatch thread
  hold      t_closed - t_taken      the batch window
  prep      t_launched - t_closed   stack, transfer, executable and guards
  complete  t_resolved - t_launched device, completion thread, future set

The four add up to the engine's latency of the frame (submit to future
set). A program whose engine keeps no stamps yields an empty timeline, and
no stages.
"""
from __future__ import annotations

import statistics

STAGES = {
    "queue": ("t_submit", "t_taken"),
    "hold": ("t_taken", "t_closed"),
    "prep": ("t_closed", "t_launched"),
    "complete": ("t_launched", "t_resolved"),
}
STAMPS = ("t_submit", "t_taken", "t_closed", "t_launched", "t_resolved")


def window_records(engine, lo: float, seconds: float) -> list:
    """The engine's records (dicts) of the requests submitted in
    ``[lo, lo + seconds)`` on its clock (``time.monotonic``), or [] if it
    keeps none. Read before the engine is closed."""
    timeline = getattr(engine, "timeline", None)
    if timeline is None:
        return []
    return [r._asdict() for r in timeline() if lo <= r.t_submit < lo + seconds]


def served(timeline) -> list:
    """Records of requests that went through every stage and got an answer."""
    return [r for r in timeline or ()
            if r["ok"] and all(r[k] is not None for k in STAMPS)]


def breakdown(timeline) -> dict:
    """Each stage's per-frame median and mean in ms over the served
    requests, their count, the mean engine latency, and how far the stage
    means' sum lies from it (relative); {} when none was served."""
    recs = served(timeline)
    if not recs:
        return {}
    out = {"frames": len(recs)}
    total = 0.0
    for stage, (a, b) in STAGES.items():
        v = [1e3 * (r[b] - r[a]) for r in recs]
        total += statistics.fmean(v)
        out[stage] = {"median_ms": statistics.median(v), "mean_ms": statistics.fmean(v)}
    lat = statistics.fmean(1e3 * (r["t_resolved"] - r["t_submit"]) for r in recs)
    out["latency_mean_ms"] = lat
    out["sum_rel_diff"] = abs(total - lat) / lat if lat > 0 else 0.0
    return out
