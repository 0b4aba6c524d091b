#!/usr/bin/env python3
"""Sweep the number of streams of an open-loop cell to find its knee: the
highest offered rate that the system sustains.

    python3 benchmarks/chip/sweep.py --workload video1080-streams \\
        --streams 2,3,4,5,6 --seconds 10 --seed 5

Each count runs one window at the cell's own size (the engine's
``max_batch`` follows the count) in one process. One JSON line per count:
offered and answered frames/s, latency quantiles, frames per dispatch, and
the median latency of the window's first and last fifths (a queue that grows
through the window shows as a last fifth far above the first).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _q(values, q):
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)] if v else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--streams", required=True, help="comma-separated counts")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    import traffic as tr

    spec = harness.resolve(args.workload)
    try:
        devices = harness.chips(spec["workload"]["chips"])
    except harness.NoChip as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 2
    harness.host_settings()
    harness.enable_compile_cache()
    for n in (int(s) for s in args.streams.split(",")):
        # each count's arrangement of camera phases is drawn from the seed
        phases = np.random.default_rng([args.seed, n]).random(n).tolist()
        over = {"traffic": {"streams": n, "phases": phases, "engine": {"max_batch": n}}}
        seen = {}
        res = harness.run(args.workload, args.seed, args.seconds, False, overrides=over,
                          devices=devices, log=lambda m: print(m, file=sys.stderr),
                          on_ctx=lambda ctx: seen.update(ctx))
        ocs = sorted(seen["outcomes"], key=lambda oc: oc.due)
        eng = seen["engine"]
        lat = [(oc.done - oc.due) * 1e3 for oc in ocs if oc.error is None]
        fifth = max(1, len(lat) // 5)
        offered = sum(tr.stream_rates(dict(spec["traffic"], streams=n)))
        print(json.dumps({
            "streams": n, "offered_fps": offered,
            "answered_fps": sum(1 for oc in ocs if oc.done <= args.seconds) / args.seconds,
            "p50_ms": _q(lat, 0.5), "p95_ms": _q(lat, 0.95), "p99_ms": _q(lat, 0.99),
            "max_ms": max(lat), "first_fifth_p50_ms": _q(lat[:fifth], 0.5),
            "last_fifth_p50_ms": _q(lat[-fifth:], 0.5),
            "frames_per_dispatch": eng["completed"] / max(1, eng["dispatches"]),
            "correct": res["correct"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
