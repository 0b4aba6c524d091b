#!/usr/bin/env python3
"""Run a benchmark cell and read what the program records of itself: the
engine's per-frame stage stamps and, with ``--trace 1``, the device's idle
time put down to the engine's spans and its busy time by name scope.

    python3 benchmarks/chip/stage_probe.py --workload paper1080-batch \\
        --seeds 11,12 --seconds 20 --trace 1

Each seed runs one window of the cell as ``run_cell.py`` runs it
(``harness.run``), all in one process. One JSON line per seed on standard
output: ``correct``; ``end_to_end``, the cell's end-to-end metrics as its
readers read them (under the profiler with ``--trace 1``; ``setup_s`` from
the call, not from process start); ``stages`` (``stages.breakdown`` of the
window's requests); and with ``--trace 1`` the result line's ``metrics``
and, from ``engine_spans.reduce``, ``idle_by_span``, ``idle_gaps`` and
``top_ops``. A program whose engine keeps no stamps and runs no spans
gives no stages, and gaps named by the client's spans alone. Exits 2 with
no accelerator, as ``run_cell.py`` does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def watch(seen: dict, seconds: float) -> None:
    """Wrap the client loops and the trace reader of this process so that
    each run leaves in ``seen`` the engine's records of its window
    (``timeline``) and, when traced, ``engine_spans.reduce`` of its trace
    (``spans``)."""
    import engine_spans
    import harness
    import stages
    import traffic

    def with_timeline(run):
        def loop(engine, *args):
            *args, on_open = args

            def opened():
                on_open()
                seen["t_open"] = time.monotonic()

            out = run(engine, *args, opened)
            engine.flush(timeout=60.0)  # every record is in the ring
            seen["timeline"] = stages.window_records(engine, seen["t_open"], seconds)
            return out
        return loop

    traffic.run_closed = with_timeline(traffic.run_closed)
    traffic.run_open = with_timeline(traffic.run_open)
    read_trace = harness.read_trace

    def read(path, n_devices):
        seen["spans"] = engine_spans.reduce(engine_spans.load_xplane(path), n_devices)
        return read_trace(path, n_devices)

    harness.read_trace = read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    import stages

    spec = harness.resolve(args.workload)
    try:
        devices = harness.chips(spec["workload"]["chips"])
    except harness.NoChip as exc:
        print(f"stage_probe: {exc}; nothing was run", file=sys.stderr)
        return 2
    harness.host_settings()
    harness.enable_compile_cache()
    seen = {}
    watch(seen, args.seconds)
    manifest = harness.load_manifest()
    for seed in (int(s) for s in args.seeds.split(",")):
        seen.clear()
        res = harness.run(args.workload, seed, args.seconds, bool(args.trace),
                          devices=devices, log=lambda m: print(m, file=sys.stderr, flush=True),
                          on_ctx=lambda ctx: seen.update(ctx=ctx))
        e2e = {}
        for m in harness.metrics_for(manifest, args.workload, "end_to_end"):
            value = harness.reader(m["name"])(seen["ctx"])
            e2e[m["name"]] = value[0] if isinstance(value, tuple) else value
        line = {"workload": args.workload, "seed": seed, "trace": args.trace,
                "correct": res["correct"], "end_to_end": e2e,
                "stages": stages.breakdown(seen.get("timeline"))}
        if args.trace:
            spans = seen["spans"]
            line["metrics"] = {k: v["value"] for k, v in res["metrics"].items()}
            line.update({k: spans[k] for k in ("idle_by_span", "idle_gaps", "top_ops")})
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
