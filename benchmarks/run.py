"""Benchmark harness: one module per paper table/figure + the kernel
hillclimb + the multi-frame throughput/sharded benches + LM substrate
micro-benches. Prints ``name,us_per_call,derived`` CSV and writes a
``BENCH_<timestamp>.json`` snapshot at the repo root.

Regression gate (``--quick``): hardware-independent **ratio rows**. A bench
module may emit rows named ``ratio/<metric>`` whose value column holds a
dimensionless speedup (e.g. batched-vs-looped fps, sharded-vs-single fps)
and whose derived column carries ``floor=<x>``; quick mode fails when any
ratio lands below its floor. Ratios compare two code paths timed in the same
process on the same host, so the gate bites on *any* machine — a fresh CI
runner needs no committed snapshot from matching hardware. Absolute
wall-clock comparison against the newest committed comparable snapshot
(same --quick mode + machine fingerprint) is still printed, but as
informational notes only — absolute times on foreign hardware say nothing
about the code.

The multi-pod roofline table is produced by repro.launch.roofline from the
dry-run artifacts (results/dryrun)."""
import argparse
import glob
import json
import os
import re
import sys
import time
import traceback

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:  # allow `python benchmarks/run.py` from anywhere
    sys.path.insert(0, REPO_ROOT)

# Informational absolute comparison only flags rows slower than this floor:
# sub-100us rows are dominated by timer/dispatch jitter, not the code.
REGRESSION_MIN_US = 100.0
REGRESSION_RATIO = 2.0


def _machine_fingerprint() -> str:
    import platform

    return f"{platform.machine()}-{os.cpu_count()}cpu"


def _load_baseline(quick: bool):
    """Newest comparable committed BENCH_*.json, or None.

    Comparable means: same --quick mode (several benches reuse row names
    between quick and full sweeps at very different sizes) and same machine
    fingerprint (absolute wall-clock on foreign hardware says nothing about
    the code — a 2x-slower CI runner is not a regression). Only git-tracked
    snapshots count as baselines ("vs the newest *committed* snapshot"): an
    uncommitted snapshot from the previous local run must not silently
    re-baseline the gate. The on-disk glob is used only when git itself is
    unavailable.
    """
    import subprocess

    try:
        out = subprocess.run(
            ["git", "ls-files", "BENCH_*.json"],
            cwd=REPO_ROOT,
            capture_output=True,
            text=True,
            timeout=30,
        )
        paths = sorted(os.path.join(REPO_ROOT, p) for p in out.stdout.split())
    except (OSError, subprocess.SubprocessError):
        paths = sorted(glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json")))
    fingerprint = _machine_fingerprint()
    for path in reversed(paths):
        try:
            with open(path) as f:
                snap = json.load(f)
            if bool(snap.get("quick")) != quick:
                continue
            if snap.get("host") != fingerprint:
                continue
            return path, {r["name"]: r for r in snap.get("rows", [])}
        except (OSError, json.JSONDecodeError, KeyError, TypeError):
            continue
    return None, None


def _write_snapshot(rows, args):
    """Write this run's rows as a new BENCH_<ts>.json at the repo root.

    Retention policy: keep the latest ~2-3 committed snapshots per machine
    fingerprint and delete older ones when committing a new one. The gate
    only ever reads the NEWEST comparable committed snapshot
    (see ``_load_baseline``), so older files are dead weight that bloats
    the repo and invites confusion about which baseline is live. Snapshots
    older than the current schema (e.g. rows missing precision provenance)
    should be the first to go.
    """
    ts = time.strftime("%Y%m%d_%H%M%S")
    path = os.path.join(REPO_ROOT, f"BENCH_{ts}.json")
    snap = {
        "timestamp": ts,
        "quick": bool(args.quick),
        "only": args.only,
        "host": _machine_fingerprint(),
        "rows": [
            {"name": n, "us_per_call": us, "derived": derived}
            for n, us, derived in rows
        ],
    }
    with open(path, "w") as f:
        json.dump(snap, f, indent=1)
    return path


def _check_regressions(rows, baseline_rows):
    """Rows >2x slower than the same-named baseline row. Returns failures."""
    failures = []
    for name, us, _ in rows:
        if name.startswith("ratio/"):
            continue  # dimensionless rows are gated by _check_ratio_gates
        old = baseline_rows.get(name)
        if old is None:
            continue
        old_us = old.get("us_per_call")
        if not isinstance(old_us, (int, float)) or old_us < REGRESSION_MIN_US:
            continue
        if us > REGRESSION_RATIO * old_us:
            failures.append((name, old_us, us))
    return failures


def _check_ratio_gates(rows):
    """Hardware-independent gate: ``ratio/*`` rows below their declared floor.

    The value column of a ratio row holds the measured speedup; the derived
    string declares the pass threshold as ``floor=<x>``. Returns a list of
    (name, floor, value) failures. Rows without a parseable floor are
    ignored (a bench may emit informational ratios).
    """
    failures = []
    for name, value, derived in rows:
        if not name.startswith("ratio/"):
            continue
        m = re.search(r"floor=([0-9.]+)", str(derived))
        if not m:
            continue
        floor = float(m.group(1))
        if value < floor:
            failures.append((name, floor, value))
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sweep sizes")
    ap.add_argument(
        "--only",
        default=None,
        help="comma list: tables,quality,kernels,throughput,sharded,video,"
        "chaos,fleet,plan_sweep,lm,roofline",
    )
    ap.add_argument(
        "--no-snapshot",
        action="store_true",
        help="skip writing the BENCH_<timestamp>.json snapshot",
    )
    args, _ = ap.parse_known_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        bench_bg_chaos,
        bench_bg_fleet,
        bench_bg_kernels,
        bench_bg_quality,
        bench_bg_sharded,
        bench_bg_tables,
        bench_bg_throughput,
        bench_lm,
        bench_plan_sweep,
        bench_roofline,
        bench_video_stream,
    )

    modules = {
        "tables": bench_bg_tables,
        "quality": bench_bg_quality,
        "kernels": bench_bg_kernels,
        "throughput": bench_bg_throughput,
        "sharded": bench_bg_sharded,
        "video": bench_video_stream,
        "chaos": bench_bg_chaos,
        "fleet": bench_bg_fleet,
        "plan_sweep": bench_plan_sweep,
        "lm": bench_lm,
        "roofline": bench_roofline,
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    # resolve the baseline BEFORE writing this run's snapshot
    baseline_path, baseline_rows = _load_baseline(quick=bool(args.quick))

    print("name,us_per_call,derived")
    failed = False
    rows = []
    for name, mod in modules.items():
        try:
            for row in mod.run(quick=args.quick):
                bench, us, derived = row
                rows.append((bench, us, derived))
                print(f"{bench},{us:.1f},{derived}", flush=True)
        except Exception:
            failed = True
            print(f"{name},ERROR,see stderr", flush=True)
            traceback.print_exc()

    if rows and not args.no_snapshot:
        snap_path = _write_snapshot(rows, args)
        print(f"# snapshot: {os.path.relpath(snap_path, REPO_ROOT)}", flush=True)

    if args.quick:
        # the gate: hardware-independent ratios vs their declared floors
        for name, floor, value in _check_ratio_gates(rows):
            print(
                f"# RATIO-REGRESSION {name}: {value:.3f} < floor {floor} "
                f"(code-path speedup collapsed — host-independent gate)",
                flush=True,
            )
            failed = True
        # informational only: absolute wall-clock vs a comparable snapshot
        if baseline_rows is not None:
            for name, old_us, new_us in _check_regressions(rows, baseline_rows):
                print(
                    f"# NOTE {name}: {old_us:.1f}us -> {new_us:.1f}us "
                    f"(>{REGRESSION_RATIO:.0f}x vs "
                    f"{os.path.basename(baseline_path)}; informational — the "
                    f"failing gate is the ratio/ rows)",
                    flush=True,
                )

    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
