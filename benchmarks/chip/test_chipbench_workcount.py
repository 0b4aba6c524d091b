"""The work count and the peaks that a roofline share is read against."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

import readings  # noqa: E402
import workcount  # noqa: E402

PIXELS = 1920 * 1080
CELLS = 92 * 162 * 4  # (1080 // 12 + 2, 1920 // 12 + 2, floor(255 / 105) + 2)


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_paper_frame_by_hand():
    work = workcount.frame_work(config("paper-1080p-r12"))
    assert workcount.grid_cells(config("paper-1080p-r12")) == CELLS == 59_616
    # 1 byte in and 1 byte out per 8-bit pixel
    assert work["bytes"] == 2 * PIXELS == 4_147_200
    # GC 4 + TI 36 per pixel, GF 31 per cell
    assert work["flops"] == 40 * PIXELS + 31 * CELLS == 84_792_096


def test_temporal_frame_by_hand():
    work = workcount.frame_work(config("video-1080p-r12-temporal"))
    # plus one read and one write of the fp32 (gx, gy, gz, 2) carry
    assert work["bytes"] == 2 * PIXELS + 2 * CELLS * 2 * 4 == 5_101_056
    # plus the EMA's 6 operations per cell
    assert work["flops"] == 40 * PIXELS + 37 * CELLS == 85_149_792


def test_least_time_is_bytes_bound_on_v5e():
    t, bound = workcount.least_time_s(config("paper-1080p-r12"), "TPU v5 lite")
    assert bound == "bytes"
    assert t == pytest.approx(4_147_200 / 819e9)
    assert 84_792_096 / 197e12 < t
    t, bound = workcount.least_time_s(config("video-1080p-r12-temporal"), "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(5_101_056 / 819e9)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        workcount.least_time_s(config("paper-1080p-r12"), "TPU v9 imaginary")


def test_share_does_not_follow_the_plan():
    """The same kernel time reads the same share whatever plan, tile or
    storage precision produced it: the count sees only the configuration."""
    from repro.core.bilateral_grid import BGConfig
    from repro.plan import plan_for

    cfg = config("paper-1080p-r12")
    bg = BGConfig(r=cfg["r"], sigma_s=cfg["sigma_s"], sigma_r=cfg["sigma_r"])
    plans = [plan_for(bg, cfg["height"], cfg["width"], n_frames=16, sharded=False,
                      cache=False, **kw)
             for kw in ({"stream_input": False}, {"stream_input": True}, {"batch_tile": 1},
                        {"batch_tile": 4}, {"precision": "bf16"})]
    assert len({p.describe() for p in plans}) == len(plans)

    class Done:
        error, done = None, 0.5

    shares = set()
    for p in plans:
        ctx = {"config": cfg, "device_kind": "TPU v5 lite", "n_devices": 1,
               "seconds": 1.0, "outcomes": [Done()] * 200, "plan": p.describe(),
               "trace": {"kernel_s": 0.1, "kernel_events": 13, "busy_s": 0.3,
                         "window_s": 1.0}}
        shares.add(readings.roofline_pct(ctx))
    assert len(shares) == 1
    value, bound = shares.pop()
    assert bound == "bytes"
    assert value == pytest.approx(100 * (4_147_200 / 819e9) / (0.1 / 200))
