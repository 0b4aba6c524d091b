#!/usr/bin/env python3
"""Run one benchmark cell of the bilateral-grid denoiser on the chip.

    python3 benchmarks/chip/run_cell.py --workload paper1080-batch \\
        --seed 7 --seconds 10 --trace 0

The cell (a configuration under a traffic mix) is named in
``BENCHMARK.json``. Set-up (JAX start, frame pools, every batch length the
window can form) counts as ``setup_s``; then the window runs for
``--seconds``, its answers are drained, and a seeded sample of them is
compared with the plain reference (``reference.py``). ``--trace 1`` runs
the same window under the profiler and reports the per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``: each number compared with its limit.
The same numbers close standard error. With no accelerator, or fewer chips
than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _process_age_s() -> float:
    """Seconds since this process started, from /proc where there is one."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.perf_counter() - _process_age_s()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness

    try:
        spec = harness.resolve(args.workload)
        devices = harness.chips(spec["workload"]["chips"])
    except harness.NoChip as exc:
        print(f"run_cell: {exc}; nothing was run", file=sys.stderr)
        return 2
    harness.host_settings()
    cache = harness.enable_compile_cache()
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    log(f"# {devices[0].device_kind} x{len(devices)}; compile cache {cache}")
    result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                         devices=devices, t_start=T_START, log=log)
    for name, c in result["checks"].items():
        op = ">=" if c.get("at_least") else "<="
        log(f"check {name} {c['value']} {op} {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
