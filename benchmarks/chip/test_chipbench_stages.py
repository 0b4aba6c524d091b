"""What the benchmark's tools read of the program's own spans and stamps:
idle gaps put down to the engine's spans, the op scopes in the breakdown
(``engine_spans.py``), and the per-frame engine stages read from the
engine's stage stamps (``stages.py``, ``stage_probe.py``)."""
import json
import os
import subprocess
import sys
from collections import namedtuple

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import engine_spans  # noqa: E402
import harness  # noqa: E402
import stage_probe  # noqa: E402
import stages  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

DEV = "/device:TPU:0"


def op(name, start, dur, scope=None, kernel=False):
    e = {"kind": "op", "device": DEV, "name": name, "kernel": kernel,
         "start": start, "dur": dur}
    if scope:
        e["scope"] = scope
    return e


def host(name, start, dur, **args):
    e = {"kind": "host", "name": name, "start": start, "dur": dur}
    if args:
        e["args"] = args
    return e


def test_span_args_from_stats_or_the_encoded_name():
    assert engine_spans.span_args("engine.prep", [("dispatch", "3"), ("n", "16")]) == (
        "engine.prep", {"dispatch": 3, "n": 16})
    assert engine_spans.span_args("engine.await#dispatch=7,n=2#") == (
        "engine.await", {"dispatch": 7, "n": 2})
    assert engine_spans.span_args("client.fetch") == ("client.fetch", {})


def test_scope_of_a_name_scope_path():
    assert engine_spans.scope_of("jit(bg_fused_streamed)/bg.relayout/pad") == "bg.relayout"
    assert engine_spans.scope_of("jit(stack_rows)/bg.ingest/concatenate") == "bg.ingest"
    assert engine_spans.scope_of("jit(fn)/pad") is None
    assert engine_spans.scope_of(None) is None


# An XSpace as tsl's own protobuf classes serialize it: a device plane whose
# two op metadata carry a name-scope path, one as an interned string
# (ref_value) and one as a str_value, beside numbers, and a host plane.
XSPACE_HEX = (
    "0af5010803120d2f6465766963653a5450553a301a111207584c41204f7073220608071005180922380807"
    "123408071218257061642e32203d206633325b325d20706164282e2e2e2922057061642e322a04080138022a"
    "0908032094b4e4f4cb0322470808124308081210256b203d20637573746f6d2d63616c6c2a2008012a1c6a69"
    "742878292f62672e6b65726e656c2f70616c6c61735f63616c6c2a0b080311000000000000f83f2a0d080312"
    "0908031205666c6f70732a2e0802122a080212266a69742862675f66757365645f73747265616d6564292f62"
    "672e72656c61796f75742f7061642a0d080112090801120574665f6f700a0b12092f686f73743a435055")


def test_op_metadata_stats_read_the_scope_paths(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(bytes.fromhex(XSPACE_HEX))
    got = engine_spans.op_metadata_stats(str(path))
    pad = {"tf_op": "jit(bg_fused_streamed)/bg.relayout/pad"}
    assert got == {"/device:TPU:0": {
        "%pad.2 = f32[2] pad(...)": pad, "pad.2": pad,
        "%k = custom-call": {"tf_op": "jit(x)/bg.kernel/pallas_call"}}}
    assert engine_spans.op_metadata_stats(str(path), prefix="/host:") == {"/host:CPU": {}}


def test_gaps_go_to_engine_spans_before_client_spans():
    events = [
        host("bench.window", 0, 1000),
        {"kind": "module", "device": DEV, "name": "jit_bg_fused_streamed",
         "start": 100, "dur": 400},
        op("%pad.2 pad", 100, 100, scope="bg.relayout"),
        op("%bg_fused_streamed custom-call", 200, 200, scope="bg.kernel", kernel=True),
        op("%copy.5 copy", 400, 100, scope="bg.trim"),
        op("%fusion.1 fusion", 700, 100),
        # dispatch thread
        host("engine.wait", 0, 80, dispatch=4, n=0),
        host("engine.prep", 500, 150, dispatch=4, n=16),
        host("engine.backpressure", 820, 180, dispatch=4, n=16),
        host("packer.pack", 510, 100, n=16),     # nested in prep: never a label
        # completion thread
        host("engine.await", 850, 100, dispatch=3, n=16),
        # client
        host("client.fetch", 500, 200),           # overlaps [500, 700) fully
        host("client.submit", 0, 100),
    ]
    r = engine_spans.reduce(events, 1)
    # gaps: [0,100) wait, [500,700) prep beats client.fetch,
    # [800,1000) backpressure -> the completion thread's await (longest
    # first, the later of two equal gaps first, as before)
    assert r["idle_gaps"] == [["engine.await", pytest.approx(200e-9)],
                              ["engine.prep", pytest.approx(200e-9)],
                              ["engine.wait", pytest.approx(100e-9)]]
    assert r["idle_by_span"] == {"engine.prep": pytest.approx(200e-9),
                                 "engine.await": pytest.approx(200e-9),
                                 "engine.wait": pytest.approx(100e-9)}
    labels = dict(r["top_ops"])
    assert labels["jit_bg_fused_streamed bg.kernel %bg_fused_streamed custom-call"] == \
        pytest.approx(200e-9)
    assert "jit_bg_fused_streamed bg.relayout %pad.2 pad" in labels
    assert "? %fusion.1 fusion" in labels  # no scope, no module: as before
    assert r["kernel_s"] == pytest.approx(200e-9) and r["kernel_events"] == 1


def test_backpressure_alone_and_the_client_fallback():
    events = [
        host("bench.window", 0, 400),
        op("a", 100, 100),
        op("b", 300, 50),
        host("engine.backpressure", 0, 90, dispatch=1, n=2),   # no completion span
        host("client.fetch", 200, 40),
        host("client.submit", 210, 80),                        # overlaps more
    ]
    r = engine_spans.reduce(events, 1)
    assert r["idle_gaps"] == [["client.submit", pytest.approx(100e-9)],
                              ["engine.backpressure", pytest.approx(100e-9)],
                              ["unattributed", pytest.approx(50e-9)]]
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_every_gap_is_labelled_not_only_the_longest():
    events = [host("bench.window", 0, 10 * (trace_reduce.TOP + 5))]
    for i in range(trace_reduce.TOP + 5):
        events.append(op("x", 10 * i + 5, 5))
        events.append(host("engine.hold", 10 * i, 5, dispatch=i, n=0))
    r = engine_spans.reduce(events, 1)
    assert len(r["idle_gaps"]) == trace_reduce.TOP
    assert r["idle_by_span"] == {"engine.hold": pytest.approx((trace_reduce.TOP + 5) * 5e-9)}


Rec = namedtuple("Rec", "uid stream_id dispatch n t_submit t_taken t_closed "
                        "t_launched t_resolved ok")


def rec(uid, t0, queue, hold, prep, complete, ok=True, dispatch=0):
    t = [t0]
    for d in (queue, hold, prep, complete):
        t.append(t[-1] + d)
    return Rec(uid, None, dispatch, 1, *t, ok)


class FakeEngine:
    def __init__(self, recs):
        self.recs = recs

    def timeline(self):
        return list(self.recs)


def test_window_records_keep_what_was_submitted_in_the_window():
    recs = [rec(0, 9.0, 1, 1, 1, 1), rec(1, 10.0, 1, 1, 1, 1),
            rec(2, 14.99, 1, 1, 1, 1), rec(3, 15.0, 1, 1, 1, 1)]
    got = stages.window_records(FakeEngine(recs), 10.0, 5.0)
    assert [r["uid"] for r in got] == [1, 2]
    assert stages.window_records(object(), 10.0, 5.0) == []  # no timeline


def test_stage_breakdown_on_a_synthetic_timeline():
    tl = [rec(i, 100.0 + i, 0.001 * i, 0.002, 0.003, 0.010 + 0.001 * i)._asdict()
          for i in range(5)]
    tl.append(rec(9, 200.0, 5.0, 5.0, 5.0, 5.0, ok=False)._asdict())  # failed
    tl.append({**rec(10, 201.0, 5.0, 5.0, 5.0, 5.0)._asdict(), "t_launched": None})
    got = stages.breakdown(tl)
    assert got["frames"] == 5
    want = {"queue": (2.0, 2.0), "hold": (2.0, 2.0), "prep": (3.0, 3.0),
            "complete": (12.0, 12.0)}
    for stage, (median, mean) in want.items():
        assert got[stage]["median_ms"] == pytest.approx(median), stage
        assert got[stage]["mean_ms"] == pytest.approx(mean), stage
    assert got["latency_mean_ms"] == pytest.approx(19.0)
    assert got["sum_rel_diff"] == pytest.approx(0.0, abs=1e-12)
    assert stages.breakdown([]) == {} and stages.breakdown(None) == {}


SMALL = {"config": {"height": 48, "width": 64},
         "traffic": {"pool_frames": 4, "outstanding": 4, "engine": {"max_batch": 2}}}
CELLS = {
    "paper1080-batch": {},
    "video1080-streams": {"traffic": {"streams": 2, "fps": 10, "phases": [0.2, 0.7],
                                      "engine": {"max_batch": 2}}},
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_probe_reads_the_window_stages_of_a_cpu_run(cell, monkeypatch):
    """Tiny frames on the CPU: the probe's wrappers hand over the window's
    records, one per answered frame, whose stages add up to the latency."""
    for name in ("run_closed", "run_open"):
        monkeypatch.setattr(traffic, name, getattr(traffic, name))
    monkeypatch.setattr(harness, "read_trace", harness.read_trace)
    seen = {}
    stage_probe.watch(seen, 0.6)
    over = harness._merge(SMALL, CELLS[cell])
    res = harness.run(cell, 2**31 + 7, 0.6, False, overrides=over, devices=jax.devices(),
                      log=lambda m: None, on_ctx=lambda ctx: seen.update(ctx=ctx))
    assert res["correct"]
    answered = [oc for oc in seen["ctx"]["outcomes"] if oc.error is None]
    got = stages.breakdown(seen["timeline"])
    assert got["frames"] == len(seen["timeline"]) == len(answered) > 0
    assert got["sum_rel_diff"] < 1e-9
    if cell == "video1080-streams":
        assert {r["stream_id"] for r in seen["timeline"]} == {0, 1}


def test_probe_runs_nothing_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "stage_probe.py"), "--workload",
         "paper1080-live", "--seeds", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2, out.stderr
    assert out.stdout == "" and "nothing was run" in out.stderr


# The PR 12 recordings (``test_chipbench_trace.py``), of a program with no
# spans or scopes: reduced as ``trace_reduce`` reduces them
@pytest.mark.parametrize("name", ["trace_batch_v5e.json", "trace_streams_v5e.json"])
def test_without_program_spans_the_reduction_is_trace_reduces(name):
    with open(os.path.join(HERE, "testdata", name)) as f:
        events = json.load(f)
    got = engine_spans.reduce(events, 1)
    assert sum(got.pop("idle_by_span").values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    assert got == trace_reduce.reduce(events, 1)


# 60 ms of a traced window of this tree on a TPU v5e, with the engine's
# spans and the op scopes (benchmarks/chip/testdata): the plan module, busy
# and kernel seconds, and kernel events, as reduced when recorded
RECORDED = {
    "trace_batch_engine_v5e.json": ("jit_bg_fused_streamed", 0.040035994, 0.028672969, 4),
    "trace_streams_engine_v5e.json": ("jit_bg_fused_temporal", 0.003853891, 0.003227649, 3),
}
XLA_ADDED = ("copy", "copy-start", "copy-done", "async-start", "async-done", "custom-call")
ENGINE_MODULE_SCOPES = {"jit_stack_rows": "bg.ingest", "jit_split_rows": "bg.split",
                        "jit_finite_rows": "bg.guard", "jit_carry_ok_rows": "bg.guard"}


def _recorded(name):
    with open(os.path.join(HERE, "testdata", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_engine_trace_names_every_gap(name):
    module, busy, kernel, n_kernel = RECORDED[name]
    r = engine_spans.reduce(_recorded(name), 1)
    assert r["window_s"] == pytest.approx(0.06)
    assert r["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert r["kernel_s"] == pytest.approx(kernel, rel=1e-9)
    assert r["kernel_events"] == n_kernel
    named = engine_spans.DISPATCH_SPANS + engine_spans.COMPLETION_SPANS
    assert set(r["idle_by_span"]) <= set(named)  # no client span, nothing unattributed
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert all(label in named for label, _ in r["idle_gaps"])
    assert r["top_ops"][0][0].startswith(f"{module} bg.kernel ")


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_engine_trace_shares_one_clock(name):
    """Each plan module starts after its dispatch's ``engine.prep`` began and
    ends before that dispatch's ``engine.await`` ended: the program's spans
    and the device planes line up."""
    module = RECORDED[name][0]
    events = _recorded(name)
    mods = sorted((e for e in events if e["kind"] == "module" and e["name"] == module),
                  key=lambda e: e["start"])
    spans = {(e["name"], e["args"]["dispatch"]): e for e in events
             if e["kind"] == "host" and e["name"] in ("engine.prep", "engine.await")}
    end = lambda e: e["start"] + e["dur"]  # noqa: E731
    first = min(d for (n, d), e in spans.items()
                if n == "engine.await" and end(e) >= end(mods[0]))
    assert len(mods) >= 3
    for i, m in enumerate(mods):
        prep, done = spans[("engine.prep", first + i)], spans[("engine.await", first + i)]
        assert prep["start"] <= m["start"] and end(m) <= end(done)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_engine_trace_ops_carry_scopes(name):
    module = RECORDED[name][0]
    events = _recorded(name)
    mods = [(e["start"], e["start"] + e["dur"], e["name"]) for e in events
            if e["kind"] == "module"]

    def module_of(t):
        return next((n for s, e, n in mods if s <= t < e), "?")

    seen = set()
    for e in events:
        if e["kind"] != "op":
            continue
        m = module_of(e["start"])
        if e["kernel"]:
            assert m == module and e["scope"] == "bg.kernel"
        elif m == module and e.get("scope"):
            assert e["scope"] in ("bg.relayout", "bg.trim", "bg.quantize")
        elif m == module:
            # what XLA adds with no source op of its own: layout copies,
            # the halves of asynchronous ones, its own (non-Mosaic) calls
            assert e["name"].split()[-1] in XLA_ADDED, e["name"]
        elif m in ENGINE_MODULE_SCOPES and e.get("scope"):
            assert e["scope"] == ENGINE_MODULE_SCOPES[m]
        seen.add(e.get("scope"))
    assert {"bg.kernel", "bg.relayout", "bg.quantize", "bg.ingest", "bg.split",
            "bg.guard"} <= seen
