"""The reduction from a profiler trace to busy, idle and kernel time: on a
hand-made trace, and on a trace recorded on a TPU v5e and trimmed."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

DEV = "/device:TPU:0"
KERNEL = ('%fn.1 = f32[18,90,12,1920]{3,2,1,0:T(8,128)} custom-call(f32[6,90,3,16,1920]'
          '{4,3,2,1,0:T(8,128)} %pad.2), custom_call_target="tpu_custom_call"')


def op(name, start, dur, kernel=False, device=DEV):
    return {"kind": "op", "device": device, "name": name, "kernel": kernel,
            "start": start, "dur": dur}


def host(name, start, dur):
    return {"kind": "host", "name": name, "start": start, "dur": dur}


def test_op_label():
    assert trace_reduce.op_label(KERNEL) == "%fn.1 custom-call"
    assert trace_reduce.op_label(
        "%copy-start = (f32[1,1920]{1,0:T(1,128)S(1)}, u32[]{:S(2)}) copy-start(%a)"
    ) == "%copy-start copy-start"


def test_hand_made_trace():
    events = [
        host("bench.window", 100, 1000),
        {"kind": "module", "device": DEV, "name": "jit_fn", "start": 150, "dur": 400},
        op("%fn.1 custom-call", 200, 300, kernel=True),
        op("%copy.1 copy", 400, 200),              # overlaps the kernel
        op("%copy.1 copy", 50, 100),               # half outside the window
        op("%slice fusion", 900, 300),             # runs past the window
        host("client.fetch", 620, 200),
        host("client.submit", 300, 50),
    ]
    r = trace_reduce.reduce(events, 1)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,150) + [200,600) + [900,1100) = 50 + 400 + 200
    assert r["busy_s"] == pytest.approx(650e-9)
    assert r["kernel_s"] == pytest.approx(300e-9) and r["kernel_events"] == 1
    # gaps: [150,200) 50, [600,900) 300 (client.fetch overlaps 200 of it)
    assert r["idle_gaps"] == [["client.fetch", pytest.approx(300e-9)],
                              ["unattributed", pytest.approx(50e-9)]]
    assert r["top_ops"][0] == ["jit_fn %fn.1 custom-call", pytest.approx(300e-9)]


def test_busy_is_averaged_over_the_cells_chips():
    events = [host("bench.window", 0, 100),
              op("a", 0, 100, device="/device:TPU:0"),
              op("a", 0, 50, device="/device:TPU:1")]
    assert trace_reduce.reduce(events, 2)["busy_s"] == pytest.approx(75e-9)
    # a chip with no operation at all counts as idle
    assert trace_reduce.reduce(events[:2], 2)["busy_s"] == pytest.approx(50e-9)


# 60 ms of the traced window of a one-chip cell on a TPU v5e: what
# load_xplane returned, cut to that span (benchmarks/chip/testdata)
RECORDED = {
    "trace_batch_v5e.json": (0.015961949, 0.009560865, 1),
    "trace_streams_v5e.json": (0.00719602, 0.004940592, 2),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_v5e_trace(name):
    with open(os.path.join(HERE, "testdata", name)) as f:
        events = json.load(f)
    busy, kernel, n_kernel = RECORDED[name]
    r = trace_reduce.reduce(events, 1)
    assert r["window_s"] == pytest.approx(0.06)
    assert r["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(1 - busy / 0.06)
    assert r["kernel_s"] == pytest.approx(kernel, rel=1e-9)
    assert r["kernel_events"] == n_kernel
    assert r["top_ops"][0][0] == "jit_fn %fn.1 custom-call"
    assert all(label in ("client.fetch", "client.submit", "unattributed")
               for label, _ in r["idle_gaps"])
