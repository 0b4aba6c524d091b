"""The async engine's per-frame stage stamps, its bounded ring of finished
requests (``AsyncFrameEngine.timeline``), the statistics read from that
ring, and the engine's profiler spans."""
import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise
from repro.data import synthetic_video
from repro.serving import AsyncFrameEngine, StageRecord, async_engine
from repro.video import MultiStreamPacker

CFG = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
STAGES = (("t_submit", "t_taken"), ("t_taken", "t_closed"),
          ("t_closed", "t_launched"), ("t_launched", "t_resolved"))


def _frames(n, h=32, w=48, seed=0):
    vid = synthetic_video(seed, n, h, w, motion=1.0)
    return [np.asarray(add_gaussian_noise(vid[t], 30.0, seed=seed + t))
            for t in range(n)]


def _by_dispatch(records):
    out = defaultdict(list)
    for r in records:
        out[r.dispatch].append(r)
    return out


def test_stages_add_up_to_the_latency_exactly():
    frames = _frames(11)
    with AsyncFrameEngine(CFG, max_batch=4, batch_window_ms=20.0) as eng:
        [f.result() for f in [eng.submit(f) for f in frames]]
        recs = eng.timeline()
    assert len(recs) == 11 and all(isinstance(r, StageRecord) for r in recs)
    for r in recs:
        stamps = [r.t_submit, r.t_taken, r.t_closed, r.t_launched, r.t_resolved]
        assert r.ok and all(t is not None for t in stamps)
        assert stamps == sorted(stamps)
        parts = [getattr(r, b) - getattr(r, a) for a, b in STAGES]
        assert parts[0] + parts[1] + parts[2] + parts[3] == r.t_resolved - r.t_submit


def test_frames_of_one_batch_share_its_dispatch_and_stamps():
    frames = _frames(10)
    with AsyncFrameEngine(CFG, max_batch=4, batch_window_ms=30.0) as eng:
        [f.result() for f in [eng.submit(f) for f in frames]]
        recs = eng.timeline()
        st = eng.stats()
    groups = _by_dispatch(recs)
    assert None not in groups and len(groups) == st.dispatches
    assert sorted(groups) == list(range(len(groups)))  # ids count dispatches
    for d, rs in groups.items():
        assert {r.n for r in rs} == {len(rs)} and len(rs) <= 4
        for field in ("t_closed", "t_launched", "t_resolved"):
            assert len({getattr(r, field) for r in rs}) == 1
    assert sum(len(rs) for rs in groups.values()) == 10


def test_ring_stays_bounded(monkeypatch):
    monkeypatch.setattr(async_engine, "TIMELINE_SIZE", 5)
    frames = _frames(3)
    with AsyncFrameEngine(CFG, max_batch=2, batch_window_ms=1.0) as eng:
        futs = [eng.submit(frames[i % 3]) for i in range(12)]
        [f.result() for f in futs]
        recs = eng.timeline()
        st = eng.stats()
    assert len(recs) == 5
    assert [r.uid for r in recs] == sorted(r.uid for r in recs)
    assert max(r.uid for r in recs) == 11
    # stats read the ring: only its five newest requests remain
    assert len(st.latency_samples) == 5 and st.completed == 12


def test_stats_are_read_from_the_ring():
    frames = _frames(9)
    with AsyncFrameEngine(CFG, max_batch=4, batch_window_ms=10.0) as eng:
        [f.result() for f in [eng.submit(f) for f in frames]]
        recs = eng.timeline()
        st = eng.stats()
    lat = sorted((r.t_resolved - r.t_submit) * 1e3 for r in recs)
    assert st.latency_samples == tuple(lat)
    assert st.latency_ms_p50 == lat[int(0.5 * len(lat))]
    assert st.latency_ms_p99 == lat[min(int(0.99 * len(lat)), len(lat) - 1)]
    sizes = [len(rs) for rs in _by_dispatch(recs).values()]
    assert st.mean_batch == pytest.approx(sum(sizes) / len(sizes))
    assert st.mean_batch == pytest.approx(9 / st.dispatches)


def test_failed_launch_is_recorded_without_a_launch_stamp():
    packer = MultiStreamPacker(CFG)
    packer.open("ok", alpha=0.0)
    frames = _frames(2)
    with AsyncFrameEngine(CFG, max_batch=2, batch_window_ms=5.0,
                          packer=packer) as eng:
        with pytest.raises(KeyError):
            eng.submit(frames[0], stream_id="ghost").result(timeout=60.0)
        eng.submit(frames[1], stream_id="ok").result(timeout=60.0)
        recs = eng.timeline()
        st = eng.stats()
    bad, good = recs
    assert not bad.ok and bad.t_launched is None and bad.t_closed is not None
    assert good.ok and good.t_launched is not None
    assert bad.dispatch != good.dispatch
    assert st.dispatches == 1 and st.mean_batch == 1.0


def test_video_mode_stamps_packs():
    sids = ("s0", "s1", "s2")
    frames = {s: _frames(4, seed=i * 7) for i, s in enumerate(sids)}
    packer = MultiStreamPacker(CFG)
    for s in sids:
        packer.open(s, alpha=0.5)
    with AsyncFrameEngine(CFG, max_batch=3, batch_window_ms=40.0,
                          packer=packer) as eng:
        futs = [eng.submit(frames[s][t], stream_id=s)
                for t in range(4) for s in sids]
        [f.result() for f in futs]
        recs = eng.timeline()
        st = eng.stats()
    assert len(recs) == 12 and all(r.ok for r in recs)
    groups = _by_dispatch(recs)
    assert len(groups) == st.dispatches
    for rs in groups.values():
        streams = [r.stream_id for r in rs]
        assert len(set(streams)) == len(streams)  # one frame per stream a pack
        assert len({(r.t_closed, r.t_launched, r.t_resolved) for r in rs}) == 1
    for r in recs:
        parts = [getattr(r, b) - getattr(r, a) for a, b in STAGES]
        assert min(parts) >= 0.0
        assert parts[0] + parts[1] + parts[2] + parts[3] == r.t_resolved - r.t_submit


def test_engine_spans_reach_the_profiler(tmp_path):
    frames = _frames(4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with AsyncFrameEngine(CFG, max_batch=2, batch_window_ms=20.0) as eng:
            [f.result() for f in [eng.submit(f) for f in frames]]
            dispatches = eng.stats().dispatches
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0]
    seen = defaultdict(set)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    args = dict(e.stats)
                    seen[e.name].add((int(args["dispatch"]), int(args["n"])))
    for name in ("engine.prep", "engine.backpressure", "engine.await", "engine.resolve"):
        assert {d for d, _ in seen[name]} == set(range(dispatches)), name
        assert sum(n for _, n in seen[name]) == 4, name
    assert seen["engine.hold"] and seen["engine.wait"]
