"""Process-isolated workers: the socket RPC, crash safety, and restores.

Every test runs under a hard SIGALRM deadline (autouse fixture): the
tentpole claim under test is "crossing the process boundary can fail, but
it can never hang", so a hung test IS the failure mode — the alarm turns
it into a loud one.

The load-bearing set:

  * ``test_subprocess_parity_bit_equal_with_local`` — the same controller
    payload served through a child process produces bit-identical results
    to the in-process worker, warm temporal carries included.
  * ``test_sigkill_mid_flight_resolves_every_future`` — SIGKILL with
    frames in flight: every future resolves (result or structured
    ``WorkerDown``), ``healthy()`` flips, nothing hangs.
  * ``test_snapshot_restore_bit_equivalence`` — a stream restored from a
    shipped snapshot continues bit-identically to one that never failed.
  * transport-fault tests — an injected dropped submit fails by sweep
    (never hangs); an injected truncation desynchronizes the framing,
    the child reconnects, and warm carries survive in the child process.
"""
import os
import signal
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.core import BGConfig
from repro.fleet import (
    LocalWorker,
    PlanController,
    PlanMismatch,
    SubprocessWorker,
    WorkerDown,
)
from repro.reliability import Fault, FaultInjector

CFG = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
H, W = 24, 32
ALPHA = 0.6
TEST_TIMEOUT_S = 180


@pytest.fixture(autouse=True)
def hard_deadline():
    """SIGALRM backstop: a hung RPC/future is the bug class under test —
    fail loudly instead of wedging the suite."""
    def on_alarm(signum, frame):
        raise AssertionError(
            f"test exceeded the {TEST_TIMEOUT_S}s hard deadline — "
            f"a worker RPC or future hung (the exact contract violation "
            f"this suite exists to catch)"
        )

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture(scope="module")
def payload():
    return PlanController(
        cfg=CFG, height=H, width=W, streams_per_worker=2,
        temporal=True, sharded=False,
    ).payload()


def _frame(seed):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 255.0, size=(H, W)).astype(np.float32)


def _sub(payload, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("batch_window_ms", 1.0)
    return SubprocessWorker("sub", payload, **kw)


# ----------------------------------------------------------------- parity
def test_subprocess_parity_bit_equal_with_local(payload):
    """4 warm frames on 2 streams: the child-process engine's outputs are
    bit-identical to the in-process engine's on the same payload."""
    frames = {s: [_frame(10 * s + t) for t in range(4)] for s in (0, 1)}
    local = LocalWorker("loc", payload, max_batch=4, batch_window_ms=1.0)
    sub = _sub(payload)
    try:
        for w in (local, sub):
            for s in (0, 1):
                w.open_stream(s, alpha=ALPHA)
        for t in range(4):
            futs = {
                (n, s): w.submit(frames[s][t], stream_id=s)
                for n, w in (("loc", local), ("sub", sub))
                for s in (0, 1)
            }
            for s in (0, 1):
                a = np.asarray(futs[("loc", s)].result(timeout=60))
                b = np.asarray(futs[("sub", s)].result(timeout=60))
                assert a.dtype == b.dtype and a.shape == b.shape
                np.testing.assert_array_equal(a, b)
        assert sorted(sub.warm_streams()) == [0, 1]
        st = sub.stats()
        assert st.completed == 8 and st.failed == 0
    finally:
        sub.close(timeout=10)
        local.close(timeout=10)


def test_uint8_answers_cross_the_codec_and_router_unchanged(payload):
    """8-bit frames through a router over a child-process worker come back
    as uint8 answers, the same levels as the in-process worker's float32
    answers to the same values, warm carries included; a float32 frame
    still comes back float32; the fleet's stats count the uint8 answers."""
    from repro.fleet import FleetRouter

    rng = np.random.default_rng(7)
    frames = {s: [rng.integers(0, 256, (H, W), dtype=np.uint8) for _ in range(3)]
              for s in (0, 1)}
    local = LocalWorker("loc", payload, max_batch=4, batch_window_ms=1.0)
    router = FleetRouter(workers=[_sub(payload)], health_interval_s=None)
    try:
        for s in (0, 1):
            local.open_stream(s, alpha=ALPHA)
            router.open_stream(s, alpha=ALPHA)
        for t in range(3):
            kind = np.float32 if t == 2 else np.uint8
            futs = {s: (router.submit(frames[s][t].astype(kind), stream_id=s),
                        local.submit(frames[s][t].astype(np.float32), stream_id=s))
                    for s in (0, 1)}
            for s, (remote, near) in futs.items():
                a = np.asarray(remote.result(timeout=60))
                b = np.asarray(near.result(timeout=60))
                assert a.dtype == kind and b.dtype == np.float32
                assert a.shape == b.shape
                np.testing.assert_array_equal(a, b)
        merged = router.stats().merged
        assert merged.completed == 6 and merged.level_answers == 4
    finally:
        router.close(timeout=10)
        local.close(timeout=10)


def test_wid_and_sid_must_be_json_plain(payload):
    with pytest.raises(TypeError, match="JSON-plain"):
        SubprocessWorker(("tuple", "wid"), payload)
    sub = _sub(payload)
    try:
        with pytest.raises(TypeError, match="JSON-plain"):
            sub.open_stream(("s", 1))
    finally:
        sub.close(timeout=10)


def test_refused_on_a_tpu_backend(payload, monkeypatch):
    """On a TPU host this process holds every chip, so a child engine could
    not load the TPU library: construction refuses before spawning."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU host"):
        _sub(payload)


def test_tampered_payload_rejected_at_construction(payload):
    """A payload whose plan_hash does not match the plan it carries must
    fail the constructor with the child's structured PlanMismatch — not
    come up as a worker serving a geometry nobody agreed to."""
    tampered = dict(payload, plan_hash="0" * len(payload["plan_hash"]))
    with pytest.raises(PlanMismatch):
        SubprocessWorker("evil", tampered, start_timeout_s=120)


# ------------------------------------------------------------ crash safety
def test_sigkill_mid_flight_resolves_every_future(payload):
    """SIGKILL with submits in flight: every future resolves within the
    sweep interval — a result or a structured WorkerDown, never a hang —
    and liveness flips without any parent-side bookkeeping."""
    sub = _sub(payload)
    try:
        sub.open_stream(0, alpha=ALPHA)
        # one warm frame so the child has compiled (the crash then lands
        # mid-serving, not mid-compile)
        np.asarray(sub.submit(_frame(0), stream_id=0).result(timeout=120))
        futs = [sub.submit(_frame(1 + t), stream_id=0) for t in range(6)]
        assert sub.healthy()
        sub.crash()  # SIGKILL the child; tell the parent nothing
        resolved = 0
        for f in futs:
            try:
                out = np.asarray(f.result(timeout=30))
                assert np.isfinite(out).all()
            except WorkerDown:
                pass
            resolved += 1
        assert resolved == len(futs)
        # detection is proc.poll-based: give the kernel a beat to reap
        t0 = time.monotonic()
        while sub.healthy() and time.monotonic() - t0 < 10.0:
            time.sleep(0.02)
        assert not sub.healthy()
        # post-mortem submits fail structurally too
        with pytest.raises(WorkerDown):
            sub.submit(_frame(99), stream_id=0).result(timeout=30)
    finally:
        sub.close(timeout=5)


def test_snapshot_restore_bit_equivalence(payload):
    """Continuation from a shipped snapshot is bit-identical to a stream
    that never failed: warm N frames on the child, snapshot, SIGKILL,
    restore onto a fresh in-process worker, serve frame N — compare with
    an uninterrupted worker fed the same sequence."""
    frames = [_frame(100 + t) for t in range(5)]
    ref = LocalWorker("ref", payload, max_batch=4, batch_window_ms=1.0)
    sub = _sub(payload)
    survivor = None
    try:
        ref.open_stream("s", alpha=ALPHA)
        sub.open_stream("s", alpha=ALPHA)
        for t in range(4):
            a = np.asarray(ref.submit(frames[t], stream_id="s")
                           .result(timeout=120))
            b = np.asarray(sub.submit(frames[t], stream_id="s")
                           .result(timeout=120))
            np.testing.assert_array_equal(a, b)
        assert sub.request_snapshot() == ["s"]
        sub.crash()
        snap = sub.carry_snapshot("s")  # parent-side store: survives death
        assert snap is not None
        assert snap.plan_hash == payload["plan_hash"]
        assert snap.frames_seen == 4
        assert np.isfinite(snap.carry).all()

        survivor = LocalWorker(
            "sv", payload, max_batch=4, batch_window_ms=1.0
        )
        survivor.open_stream("s", alpha=ALPHA)
        assert survivor.restore_carry("s", snap)
        want = np.asarray(ref.submit(frames[4], stream_id="s")
                          .result(timeout=120))
        got = np.asarray(survivor.submit(frames[4], stream_id="s")
                         .result(timeout=120))
        np.testing.assert_array_equal(got, want)
    finally:
        sub.close(timeout=5)
        ref.close(timeout=10)
        if survivor is not None:
            survivor.close(timeout=10)


# -------------------------------------------------------- transport faults
def test_dropped_submit_fails_by_sweep_never_hangs(payload):
    """An injected drop_message on a submit: the bytes vanish, the child
    never sees the frame — the parent's sweep must fail the future with
    WorkerDown after submit_timeout_s. 'Message lost' can cost latency,
    never a hang."""
    inj = FaultInjector([Fault(kind="drop_message", message="submit",
                               times=1)])
    sub = _sub(payload)
    try:
        sub.open_stream(0, alpha=ALPHA)
        # warm clean first under the default sweep timeout (the compile
        # frame must not race a short one), then shorten the timeout and
        # install the injector for the faulted phase
        np.asarray(sub.submit(_frame(0), stream_id=0).result(timeout=120))
        sub.submit_timeout_s = 2.0
        sub.fault_injector = inj
        fut = sub.submit(_frame(1), stream_id=0)  # this one is dropped
        with pytest.raises(WorkerDown, match="unresolved"):
            fut.result(timeout=30)
        assert inj.fired == [1]
        sub.fault_injector = None
        # the worker itself is fine — the next frame serves normally
        out = np.asarray(sub.submit(_frame(2), stream_id=0)
                         .result(timeout=120))
        assert np.isfinite(out).all()
    finally:
        sub.close(timeout=10)


def test_truncated_message_reconnects_and_carries_survive(payload):
    """An injected truncation desynchronizes the child's framing: the
    torn frame must decode to a structured CodecError child-side, the
    child re-dials, and — because the engine lives on across reconnects —
    the stream's warm carry survives bit-for-bit (frames_seen keeps
    counting, no quarantine)."""
    inj = FaultInjector([Fault(kind="truncate_message", message="submit",
                               fraction=0.5, times=1)])
    sub = _sub(payload)
    try:
        sub.open_stream(0, alpha=ALPHA)
        np.asarray(sub.submit(_frame(0), stream_id=0).result(timeout=120))
        sub.fault_injector = inj  # faulted phase starts after the warm-up
        fut = sub.submit(_frame(1), stream_id=0)  # truncated on the wire
        with pytest.raises(WorkerDown):
            fut.result(timeout=60)
        # the child reconnects on its own (bounded backoff); the next
        # submit may race the re-handshake, so retry briefly
        t0 = time.monotonic()
        out = None
        while time.monotonic() - t0 < 30.0:
            try:
                out = np.asarray(
                    sub.submit(_frame(2), stream_id=0).result(timeout=60)
                )
                break
            except WorkerDown:
                time.sleep(0.05)
        assert out is not None and np.isfinite(out).all()
        assert sub.reconnects >= 1
        assert sub.warm_streams() == [0]
        snap = sub.request_snapshot() and sub.carry_snapshot(0)
        # the carry kept accumulating across the tear: the dropped frame
        # never reached the engine, the two served frames did
        assert snap.frames_seen == 2
        assert inj.fired == [1]
    finally:
        sub.close(timeout=10)


def test_stalled_heartbeats_flip_liveness_without_process_death(payload):
    """A wedged child (alive, not heartbeating) must go unhealthy after
    heartbeat_timeout_s — the watchdog's signal for hung-but-running
    processes — and recover when heartbeats resume."""
    inj = FaultInjector([Fault(kind="delay_heartbeat", delay_s=1.6,
                               message="heartbeat", times=1)])
    sub = _sub(payload, fault_injector=inj, heartbeat_interval_s=0.1,
               heartbeat_timeout_s=0.5)
    try:
        sub.open_stream(0, alpha=ALPHA)
        # the fault fires on the first transport message and opens the
        # suppression window; watch liveness flip, then recover once the
        # window expires and heartbeats land again
        saw_unhealthy = False
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0:
            if not sub.healthy():
                saw_unhealthy = True
                break
            time.sleep(0.05)
        assert saw_unhealthy, "heartbeat staleness never flipped healthy()"
        assert sub._proc.poll() is None  # the process is alive: wedged != dead
        t0 = time.monotonic()
        while not sub.healthy() and time.monotonic() - t0 < 10.0:
            time.sleep(0.05)
        assert sub.healthy(), "liveness did not recover after the window"
    finally:
        sub.close(timeout=10)
