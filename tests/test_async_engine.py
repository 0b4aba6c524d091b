"""Async frame engine: equivalence with the plan's own answer, video-mode
stream ordering, backpressure, deadlines, and argument validation.

Wall-clock-sensitive tests (those asserting *when* a dispatch happens, not
just that it happens) carry ``@pytest.mark.timing`` so loaded CI runners can
run the suite with ``-m "not timing"``. When they do run, their wall-clock
budgets auto-relax with the host's run-queue pressure (``os.getloadavg``),
and they skip outright on a heavily oversubscribed host — a scheduling-delay
assertion says nothing about the code when every thread is time-slicing
(see :func:`_timing_relax`). Everything else is scheduling-order
independent: futures resolve whenever the background threads get there.

The engine serves the plan it is given: here a fused plan on a batch mesh
over all local devices, so on a multi-device host (the forced 8-device CI
mesh) every dispatch is sharded — the same assertions hold because sharding
is bit-invisible (test_bg_sharded.py, test_mesh_engine.py).
"""
import os
import queue
import time

import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise
from repro.data import synthetic_video
from repro.plan import BGPlan
from repro.serving import AsyncFrameEngine
from repro.sharding.bg_shard import batch_mesh
from repro.video import MultiStreamPacker

CFG = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
# the service plan: a batch mesh over all local devices (one device: none)
PLAN = BGPlan(cfg=CFG, mesh=batch_mesh())

# per-CPU 1-minute load above which wall-clock assertions are meaningless
# (every thread is time-slicing; dispatch latency measures the scheduler,
# not the engine) — skip rather than flake
_TIMING_SKIP_LOAD = 4.0


def _timing_relax() -> float:
    """Budget multiplier for wall-clock assertions on a contended host.

    Returns ``max(1, per-cpu 1-minute load)``: a box running at 2x
    oversubscription legitimately doubles thread wake-up latency, so the
    deadline/window budgets scale with it instead of flaking. Sampled
    *before* the timed section (load is backward-looking). Skips the caller
    when the host is so loaded the assertion would only measure contention.
    """
    try:
        load = os.getloadavg()[0] / max(os.cpu_count() or 1, 1)
    except (AttributeError, OSError):  # platform without getloadavg
        return 1.0
    if load > _TIMING_SKIP_LOAD:
        pytest.skip(
            f"host oversubscribed (load/cpu = {load:.1f} > "
            f"{_TIMING_SKIP_LOAD}): wall-clock assertions measure the "
            f"scheduler, not the engine"
        )
    return max(1.0, load)


def _frames(n, h=32, w=48, seed=0):
    vid = synthetic_video(seed, n, h, w, motion=1.0)
    return [
        np.asarray(add_gaussian_noise(vid[t], 30.0, seed=seed + t))
        for t in range(n)
    ]


def test_results_match_plan_on_stacked_frames():
    frames = _frames(11)
    ref = np.asarray(PLAN(np.stack(frames)))

    with AsyncFrameEngine(plan=PLAN, max_batch=4, batch_window_ms=20.0) as eng:
        futs = [eng.submit(f) for f in frames]
        for i, fut in enumerate(futs):
            np.testing.assert_array_equal(np.asarray(fut.result()), ref[i])
        st = eng.stats()
    assert st["submitted"] == st["completed"] == 11
    assert st["dispatches"] >= 3  # max_batch 4 caps every micro-batch
    assert st["latency_ms_p99"] >= st["latency_ms_p50"] > 0.0


def test_video_mode_matches_solo_packer():
    """Frames fan out over 3 streams through the engine; each stream's output
    sequence must equal running that stream alone through a fresh packer —
    per-request futures, per-stream order, no cross-stream state."""
    n_frames, sids = 5, ("s0", "s1", "s2")
    per_stream = {s: _frames(n_frames, seed=i * 11) for i, s in enumerate(sids)}
    alphas = {"s0": 0.5, "s1": 0.0, "s2": 0.7}

    packer = MultiStreamPacker(PLAN)
    for s in sids:
        packer.open(s, alpha=alphas[s])
    with AsyncFrameEngine(
        packer=packer, max_batch=len(sids), batch_window_ms=20.0
    ) as eng:
        futs = [
            (s, t, eng.submit(per_stream[s][t], stream_id=s))
            for t in range(n_frames)
            for s in sids
        ]
        outs = {(s, t): np.asarray(f.result()) for s, t, f in futs}

    for s in sids:
        solo = MultiStreamPacker(PLAN)
        solo.open(s, alpha=alphas[s])
        for t in range(n_frames):
            ref = solo.pack({s: per_stream[s][t]})[s]
            np.testing.assert_array_equal(np.asarray(ref), outs[(s, t)])


def test_video_mode_defers_same_stream_frames():
    """Two frames of one stream never share a micro-batch: the second defers
    to the next dispatch and still resolves in order."""
    frames = _frames(6, seed=3)
    packer = MultiStreamPacker(PLAN)
    packer.open("only", alpha=0.6)
    with AsyncFrameEngine(
        packer=packer, max_batch=8, batch_window_ms=5.0
    ) as eng:
        futs = [eng.submit(f, stream_id="only") for f in frames]
        [f.result() for f in futs]
        st = eng.stats()
    assert st["dispatches"] == 6 and st["mean_batch"] == 1.0
    assert packer.sessions["only"].frames_seen == 6


def test_backpressure_and_flush():
    frames = _frames(1)
    with AsyncFrameEngine(
        plan=PLAN, max_batch=1, max_queue=2, batch_window_ms=0.0
    ) as eng:
        rejected = 0
        futs = []
        for _ in range(50):
            try:
                futs.append(eng.submit(frames[0], block=False))
            except queue.Full:
                rejected += 1
        assert rejected > 0  # the bounded queue sheds load
        assert eng.flush(timeout=60.0)
        assert all(f.done() for f in futs)
        st = eng.stats()
        assert st["submitted"] == st["completed"] == len(futs)


def test_dispatch_errors_fail_futures_not_engine():
    packer = MultiStreamPacker(PLAN)
    packer.open("ok", alpha=0.0)
    frames = _frames(2)
    with AsyncFrameEngine(
        packer=packer, max_batch=2, batch_window_ms=5.0
    ) as eng:
        bad = eng.submit(frames[0], stream_id="ghost")  # stream never opened
        with pytest.raises(KeyError):
            bad.result(timeout=60.0)
        good = eng.submit(frames[1], stream_id="ok")  # engine still serves
        assert good.result(timeout=60.0).shape == frames[1].shape


def test_cancelled_future_does_not_kill_engine():
    """A client cancelling a pending future must not crash the completion
    thread — later requests (even batch-mates of the cancelled one) still
    resolve."""
    frames = _frames(2)
    with AsyncFrameEngine(plan=PLAN, max_batch=64, batch_window_ms=150.0) as eng:
        f1 = eng.submit(frames[0])
        f1.cancel()  # races the window; both outcomes must be survivable
        f2 = eng.submit(frames[1])
        assert f2.result(timeout=60.0).shape == frames[1].shape
        assert f1.cancelled() or f1.done()
        eng.submit(frames[0]).result(timeout=60.0)  # engine still serves


def test_validation_and_lifecycle():
    for bad_kw in (
        {"max_batch": 0},
        {"max_batch": -2},
        {"max_queue": 0},
        {"max_inflight": 0},
    ):
        with pytest.raises(ValueError):
            AsyncFrameEngine(plan=PLAN, **bad_kw)
    with pytest.raises(TypeError, match="plan_for"):
        AsyncFrameEngine(max_batch=2)  # neither plan= nor packer=

    eng = AsyncFrameEngine(packer=MultiStreamPacker(PLAN), max_batch=2)
    with pytest.raises(ValueError):
        eng.submit(_frames(1)[0])  # video mode requires a stream_id
    eng.close()
    eng.close()  # idempotent
    with pytest.raises(RuntimeError):
        eng.submit(_frames(1)[0], stream_id="x")


@pytest.mark.timing
def test_deadline_forces_early_dispatch():
    """A lone frame with a 30ms budget must not wait out the batch window.

    The window scales with the load relaxation alongside the assertion
    budget, so the pass/fail gap (budget < window) survives any relax
    factor — a broken deadline path always waits out the full window and
    always overshoots the budget."""
    relax = _timing_relax()  # sample load before the timed section
    frames = _frames(1)
    with AsyncFrameEngine(plan=PLAN, max_batch=64, batch_window_ms=500.0 * relax) as eng:
        eng.submit(frames[0]).result()  # warm-up compile outside the clock
        t0 = time.monotonic()
        # budget scales with load too: the PR-6 collect-time shedder fails a
        # request whose deadline already passed, so a fixed 30ms budget on a
        # slow box would test the shed path instead of the early dispatch
        eng.submit(frames[0], deadline_ms=30.0 * relax).result()
        dt = time.monotonic() - t0
    assert dt < 0.4 * relax, f"deadline ignored: {dt * 1e3:.0f}ms (relax={relax:.1f})"


@pytest.mark.timing
def test_batch_window_expiry_dispatches_partial_batch():
    """Low traffic: a never-full batch still dispatches after the window."""
    relax = _timing_relax()  # sample load before the timed section
    frames = _frames(2)
    with AsyncFrameEngine(plan=PLAN, max_batch=64, batch_window_ms=40.0) as eng:
        eng.submit(frames[0]).result()  # warm-up compile outside the clock
        t0 = time.monotonic()
        out = eng.submit(frames[1]).result()
        dt = time.monotonic() - t0
        st = eng.stats()
    assert out.shape == frames[1].shape
    assert st["mean_batch"] == 1.0 and dt < 2.0 * relax
