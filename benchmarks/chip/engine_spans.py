"""Read a profiler trace of one window together with what the program names
of itself: the engine's spans and the device operations' name scopes.

Where the program names its stages, the trace carries them: the engine's
spans (``engine.wait``, ``engine.hold``, ``engine.prep``,
``engine.backpressure`` on its dispatch thread, ``engine.await`` and
``engine.resolve`` on its completion thread, ``packer.pack`` inside
``engine.prep``) with their ``dispatch`` id and batch size ``n`` as
arguments, and on each device operation the ``jax.named_scope`` path it was
traced under (``bg.relayout``, ``bg.kernel``, ...). A v5e trace keeps that
path in the stats of the op's event *metadata* (``SCOPE_STAT``), which
``ProfileData`` does not expose, so :func:`op_metadata_stats` reads them from
the file itself.

:func:`load_xplane` gives ``trace_reduce``'s plain events, with ``scope`` on
the ops that have one and the engine's spans (with ``args``) among the host
events. :func:`reduce` is ``trace_reduce.reduce`` with the ``top_ops``
labelled by scope and every idle gap named by :func:`gap_labels`, plus
``idle_by_span``. A trace of a program without spans or scopes reduces as
``trace_reduce`` reduces it, but for the gaps that no client span overlaps.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

import trace_reduce as tr

DISPATCH_SPANS = ("engine.wait", "engine.hold", "engine.prep", "engine.backpressure")
COMPLETION_SPANS = ("engine.await", "engine.resolve")
PROGRAM_SPAN_PREFIXES = ("engine.", "packer.")
SCOPE_STAT = "tf_op"  # the device op's name-scope path, as a v5e trace has it
SCOPE_PREFIX = "bg."


def span_args(name: str, stats=()) -> tuple:
    """``(name, args)`` of a host event: a ``TraceAnnotation``'s keyword
    arguments arrive as the event's stats, or encoded in its name as
    ``name#key=value,...#``; whole numbers are read as ints."""
    args = dict(stats)
    if "#" in name:
        name, _, enc = name.partition("#")
        for kv in enc.rstrip("#").split(","):
            k, sep, v = kv.partition("=")
            if sep:
                args[k] = v
    out = {}
    for k, v in args.items():
        try:
            out[k] = int(v)
        except (TypeError, ValueError):
            out[k] = v
    return name, out


def scope_of(path) -> str | None:
    """The innermost ``bg.*`` component of a name-scope path such as
    ``jit(bg_fused_streamed)/bg.relayout/pad``, else None."""
    parts = [p for p in str(path or "").split("/") if p.startswith(SCOPE_PREFIX)]
    return parts[-1] if parts else None


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field, value)`` of one protobuf message: an int for a varint, the
    bytes (a memoryview) for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_metadata_stats(path: str, prefix: str = tr.DEVICE_PREFIX) -> dict:
    """``{plane name: {event name: {stat name: value}}}`` for the planes of
    an ``.xplane.pb`` (tsl's ``XSpace``) whose names start with ``prefix``
    (the devices): the stats that each event's metadata carries. Strings
    and interned strings (``ref_value``) only, which is where the op's
    name-scope path is."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:  # XPlane.name
                name = bytes(v).decode()
                if not name.startswith(prefix):
                    break
            elif pf == 4:  # XPlane.event_metadata: map entry, value = field 2
                metas += [mv for k, mv in _fields(v) if k == 2]
            elif pf == 5:  # XPlane.stat_metadata: id -> XStatMetadata.name
                for k, mv in _fields(v):
                    if k == 2:
                        sm = dict(_fields(mv))
                        stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if not name.startswith(prefix):
            continue
        table = {}
        for mv in metas:
            ev_names, stats = [], {}
            for k, v in _fields(mv):
                if k in (2, 4):  # XEventMetadata.name, .display_name
                    ev_names.append(bytes(v).decode())
                elif k == 5:  # XEventMetadata.stats
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1, 0), "")
                    if 5 in st:  # str_value
                        stats[key] = bytes(st[5]).decode()
                    elif 7 in st:  # ref_value: an interned string
                        stats[key] = stat_names.get(st[7], "")
            for n in ev_names:
                if n:
                    table[n] = stats
        out[name] = table
    return out


def load_xplane(trace_dir: str) -> list:
    """``trace_reduce.load_xplane``'s events from the newest ``*.xplane.pb``
    under ``trace_dir``; an op traced under a ``bg.*`` name scope also has
    ``scope``, and the engine's and packer's spans are host events with
    ``args``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    meta = op_metadata_stats(paths[-1])
    keep_host = (tr.WINDOW,) + tr.CLIENT_SPANS
    events = []
    for plane in data.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            scopes = {n: scope_of(st.get(SCOPE_STAT))
                      for n, st in meta.get(plane.name, {}).items()}
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    for e in line.events:
                        ev = {"kind": "op", "device": plane.name, "name": tr.op_label(e.name),
                              "kernel": tr.KERNEL_MARK in e.name,
                              "start": e.start_ns, "dur": e.duration_ns}
                        if scopes.get(e.name):
                            ev["scope"] = scopes[e.name]
                        events.append(ev)
                elif line.name == tr.MODULES_LINE:
                    events += [{"kind": "module", "device": plane.name,
                                "name": e.name.split("(", 1)[0],
                                "start": e.start_ns, "dur": e.duration_ns}
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep_host:
                        events.append({"kind": "host", "name": e.name,
                                       "start": e.start_ns, "dur": e.duration_ns})
                    elif e.name.startswith(PROGRAM_SPAN_PREFIXES):
                        name, args = span_args(e.name, e.stats)
                        events.append({"kind": "host", "name": name, "args": args,
                                       "start": e.start_ns, "dur": e.duration_ns})
    return events


def idle_gaps(events: list) -> list:
    """Every ``(start, end)`` in which the first device ran nothing inside
    the window, as ``trace_reduce.reduce`` draws the window and the busy
    intervals."""
    win = [e for e in events if e["kind"] == "host" and e["name"] == tr.WINDOW]
    ops = [e for e in events if e["kind"] == "op"]
    if win:
        lo, hi = win[0]["start"], win[0]["start"] + win[0]["dur"]
    elif ops:
        lo = min(e["start"] for e in ops)
        hi = max(e["start"] + e["dur"] for e in ops)
    else:
        return []
    first = min((e["device"] for e in ops), default=None)
    iv = [tr._clip(e["start"], e["start"] + e["dur"], lo, hi) for e in ops
          if e["device"] == first]
    busy = tr._union([(s, t) for s, t in iv if t > s])
    edges = [lo] + [x for s, t in busy for x in (s, t)] + [hi]
    return [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]


def _best_spans(gaps: list, spans: list) -> list:
    """For each gap ``(g0, g1)`` (sorted, disjoint), the ``(overlap, name)``
    of the span ``(s, t, name)`` that overlaps it most (ties to the larger
    name), or None where no span overlaps it."""
    best = [None] * len(gaps)
    ends = [g1 for _, g1 in gaps]
    starts = [g0 for g0, _ in gaps]
    for s, t, name in spans:
        for i in range(bisect.bisect_right(ends, s), bisect.bisect_left(starts, t)):
            cand = (tr._overlap(gaps[i][0], gaps[i][1], s, t), name)
            if cand[0] > 0 and (best[i] is None or cand > best[i]):
                best[i] = cand
    return best


def gap_labels(gaps: list, events: list) -> list:
    """The host span each device idle gap is put down to: the engine
    dispatch thread's span that overlaps it most, or, where that span is
    ``engine.backpressure``, the completion thread's; where no engine span
    overlaps it, the client span that overlaps it most; else
    ``unattributed``."""
    def spans(names):
        return [(e["start"], e["start"] + e["dur"], e["name"]) for e in events
                if e["kind"] == "host" and e["name"] in names]

    disp = _best_spans(gaps, spans(DISPATCH_SPANS))
    comp = _best_spans(gaps, spans(COMPLETION_SPANS))
    client = _best_spans(gaps, spans(tr.CLIENT_SPANS))
    out = []
    for d, c, k in zip(disp, comp, client):
        if d is not None:
            out.append(c[1] if d[1] == "engine.backpressure" and c is not None else d[1])
        else:
            out.append(k[1] if k is not None else "unattributed")
    return out


def reduce(events: list, n_devices: int) -> dict:
    """``trace_reduce.reduce`` of ``events``, each ``top_ops`` label with the
    op's ``bg.*`` scope where it has one (``<module> <scope> <op>``), the
    ten longest ``idle_gaps`` named by :func:`gap_labels`, and
    ``idle_by_span``: every gap's length summed by that name."""
    scoped = [dict(e, name=f"{e['scope']} {e['name']}") if e.get("scope") else e
              for e in events]
    out = tr.reduce(scoped, n_devices)
    gaps = idle_gaps(events)
    labels = gap_labels(gaps, events)
    by_span = defaultdict(float)
    for (g0, g1), label in zip(gaps, labels):
        by_span[label] += (g1 - g0) / 1e9
    longest = sorted(((g1 - g0, g0, label) for (g0, g1), label in zip(gaps, labels)),
                     reverse=True)[:tr.TOP]
    out["idle_gaps"] = [[label, ns / 1e9] for ns, _, label in longest]
    out["idle_by_span"] = dict(sorted(by_span.items(), key=lambda kv: -kv[1]))
    return out
