#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds in one process,
to set their limits: the program as the configuration states it (the lower
reading), or its control (the upper reading).

    python3 benchmarks/chip/limits.py --workload paper1080-batch \\
        --seeds 11,12,13 --seconds 10 [--control]

``--control`` runs the program's own bf16 storage path, one precision below
the configuration's fp32. Each run is a whole window through the engine at
the cell's own size and load; one JSON line per seed gives its checks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness

    spec = harness.resolve(args.workload)
    try:
        devices = harness.chips(spec["workload"]["chips"])
    except harness.NoChip as exc:
        print(f"limits: {exc}", file=sys.stderr)
        return 2
    harness.host_settings()
    harness.enable_compile_cache()
    over = {"config": {"precision": "bf16"}} if args.control else None
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run(args.workload, seed, args.seconds, False, overrides=over,
                          devices=devices,
                          log=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "correct": res["correct"],
                          "checks": res["checks"], "metrics": res["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
