"""Reduce a profiler trace of one window to device busy time, kernel time
and the breakdown that the result line carries.

What a TPU trace holds (read by hand from a v5e trace of the batch cell):
each chip is a plane ``/device:TPU:<i>`` whose line ``XLA Ops`` has one
event per HLO operation run, named by the HLO text
(``%fn.1 = f32[18,90,12,1920]{...} custom-call(...), custom_call_target=
"tpu_custom_call", ...``), and whose line ``XLA Modules`` has one event per
program run (``jit_fn(<hash>)``). The Pallas kernels are the Mosaic custom
calls, ``custom_call_target="tpu_custom_call"``: on the denoiser's path the
only ones. Host threads are lines of the plane ``/host:CPU``; the client's
``TraceAnnotation`` spans (``bench.window``, ``client.submit``,
``client.fetch``) are events there. Host and device events share one clock.

:func:`load_xplane` turns the trace file into a short list of plain events;
:func:`reduce` works on that list alone, so a recorded and trimmed list is
enough to check it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
WINDOW = "bench.window"
CLIENT_SPANS = ("client.submit", "client.fetch")
TOP = 10

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_label(hlo_text: str) -> str:
    """``%fn.1 custom-call`` from an HLO instruction's text."""
    name = hlo_text.split(" = ", 1)[0]
    m = _OPCODE.search(hlo_text)
    return f"{name} {m.group(1)}" if m else name


def load_xplane(trace_dir: str) -> list:
    """Plain events from the newest ``*.xplane.pb`` under ``trace_dir``:
    dicts with ``kind`` ("op", "module" or "host"), ``device`` (plane
    name, device events only), ``name``, ``kernel`` (ops only), ``start``
    and ``dur`` in nanoseconds."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    keep_host = (WINDOW,) + CLIENT_SPANS
    events = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events += [{"kind": "op", "device": plane.name, "name": op_label(e.name),
                                "kernel": KERNEL_MARK in e.name,
                                "start": e.start_ns, "dur": e.duration_ns}
                               for e in line.events]
                elif line.name == MODULES_LINE:
                    events += [{"kind": "module", "device": plane.name,
                                "name": e.name.split("(", 1)[0],
                                "start": e.start_ns, "dur": e.duration_ns}
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [{"kind": "host", "name": e.name, "start": e.start_ns,
                            "dur": e.duration_ns}
                           for e in line.events if e.name in keep_host]
    return events


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(events: list, n_devices: int) -> dict:
    """Busy, idle and kernel time over the traced window.

    The window is the host span ``bench.window`` (else the extent of the
    device events). ``busy_s`` is the union of the intervals in which an
    operation ran, averaged over the ``n_devices`` device planes;
    ``kernel_s`` is the summed duration of the Mosaic custom calls over all
    devices. ``top_ops`` lists the operations (by module and HLO name) that
    took most device time; ``idle_gaps`` the longest gaps on the first
    device, each named by the client span that overlaps it most, else
    ``unattributed``.
    """
    win = [e for e in events if e["kind"] == "host" and e["name"] == WINDOW]
    ops = [e for e in events if e["kind"] == "op"]
    if win:
        lo, hi = win[0]["start"], win[0]["start"] + win[0]["dur"]
    elif ops:
        lo = min(e["start"] for e in ops)
        hi = max(e["start"] + e["dur"] for e in ops)
    else:
        lo = hi = 0.0
    devices = sorted({e["device"] for e in ops})
    modules = defaultdict(list)
    for e in events:
        if e["kind"] == "module":
            modules[e["device"]].append((e["start"], e["start"] + e["dur"], e["name"]))
    starts = {}
    for dev, v in modules.items():
        v.sort()
        starts[dev] = [s for s, _, _ in v]

    def module_of(dev, t):
        i = bisect.bisect_right(starts.get(dev, []), t) - 1
        if i >= 0 and t < modules[dev][i][1]:
            return modules[dev][i][2]
        return "?"

    busy = {}
    kernel_ns = 0.0
    n_kernel = 0
    per_label = defaultdict(float)
    for dev in devices:
        iv = []
        for e in ops:
            if e["device"] != dev:
                continue
            s, t = _clip(e["start"], e["start"] + e["dur"], lo, hi)
            if t <= s:
                continue
            iv.append((s, t))
            if e["kernel"]:
                kernel_ns += t - s
                n_kernel += 1
            per_label[f"{module_of(dev, e['start'])} {e['name']}"] += t - s
        busy[dev] = _union(iv)
    busy_ns = [sum(t - s for s, t in busy.get(d, [])) for d in devices]
    busy_ns += [0.0] * max(0, n_devices - len(busy_ns))
    spans = [(e["start"], e["start"] + e["dur"], e["name"]) for e in events
             if e["kind"] == "host" and e["name"] in CLIENT_SPANS]
    first = busy.get(devices[0], []) if devices else []
    edges = [lo] + [x for s, t in first for x in (s, t)] + [hi]
    longest = sorted(((g1 - g0, g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                      if g1 > g0), reverse=True)[:TOP]
    gaps = []
    for length, g0, g1 in longest:
        best = max(((_overlap(g0, g1, s, t), n) for s, t, n in spans), default=(0.0, ""))
        gaps.append((length, best[1] if best[0] > 0 else "unattributed"))
    top = sorted(per_label.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / max(1, n_devices) / 1e9,
        "kernel_s": kernel_ns / 1e9,
        "kernel_events": n_kernel,
        "top_ops": [[name, ns / 1e9] for name, ns in top],
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:TOP]],
    }
