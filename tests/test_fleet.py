"""Fleet serving layer: affinity, plan distribution, failure containment,
backpressure, and telemetry rollup.

The load-bearing quartet:

  * ``test_affinity_invariant_1k_frames`` — across 1000 frames a warm
    stream's frames land on exactly one worker (``streams_served`` evidence
    on every worker) and the affinity table never silently moves.
  * ``test_worker_kill_quarantines_exactly_victim_streams`` — a worker
    death resets precisely its own streams' carries; survivors' carry
    objects are untouched (asserted by identity), and every migration in
    ``rebalance_log`` was preceded by a quarantine.
  * ``test_mixed_plan_hash_rejected_at_construction`` — a fleet whose
    workers disagree on ``plan_hash`` never comes up.
  * ``test_router_sheds_before_worker_queue_overflows`` — under a wedged
    worker the router's ``max_worker_queue`` bound fires (structured
    ``FleetSaturated``) while the worker's own request queue stays far
    from capacity.

Everything is scheduling-order independent: the watchdog thread is
disabled (``health_interval_s=None``) and failures are injected or
triggered synchronously.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.core import BGConfig
from repro.fleet import (
    FleetRouter,
    FleetSaturated,
    FleetWatchdog,
    LocalWorker,
    PlanController,
    PlanMismatch,
)
from repro.plan import plan_for
from repro.plan_cache import PlanCache
from repro.reliability import Fault, FaultInjector, FaultPlan
from repro.serving import EngineStats

CFG = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
H, W = 24, 32
ALPHA = 0.6


def _controller(streams_per_worker=4, **kw):
    return PlanController(
        cfg=CFG, height=H, width=W,
        streams_per_worker=streams_per_worker, temporal=True,
        sharded=False, **kw,
    )


def _fleet(n_workers=2, **kw):
    kw.setdefault("health_interval_s", None)  # deterministic: no poller
    kw.setdefault("worker_kwargs", dict(max_batch=8, batch_window_ms=1.0))
    kw.setdefault("controller", _controller())
    return FleetRouter(n_workers=n_workers, **kw)


def _frame(seed):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 255.0, size=(H, W)).astype(np.float32)


# --------------------------------------------------------------- affinity
def test_affinity_invariant_1k_frames():
    """1000 frames over 8 warm streams on 2 workers: every stream's frames
    land on exactly the worker its affinity entry names, and nothing ever
    migrates (no failures -> empty rebalance_log)."""
    n_streams, rounds = 8, 125
    frames = [_frame(s) for s in range(n_streams)]
    with _fleet(n_workers=2) as router:
        pins = {s: router.open_stream(s, alpha=ALPHA) for s in range(n_streams)}
        assert set(pins.values()) <= {0, 1}
        for t in range(rounds):
            futs = [
                router.submit(frames[s], stream_id=s)
                for s in range(n_streams)
            ]
            for f in futs:
                assert np.isfinite(np.asarray(f.result())).all()
            # the pin never moves while the stream is warm
            assert {s: router.stream_worker(s) for s in range(n_streams)} \
                == pins
        # per-worker accounting: each stream served by exactly its pin
        for s in range(n_streams):
            served_on = {
                w.wid for w in router.workers
                if w.streams_served.get(s, 0) > 0
            }
            assert served_on == {pins[s]}, (s, served_on, pins[s])
            assert router.workers[pins[s]].streams_served[s] == rounds
        assert router.rebalance_log == []
        assert router.rebalanced_streams == 0
        st = router.stats()
        assert st.merged.completed == n_streams * rounds
        assert st.merged.failed == 0


def test_temporal_fleet_requires_stream_id():
    with _fleet(n_workers=2) as router:
        with pytest.raises(ValueError, match="stream_id"):
            router.submit(_frame(0))
        with pytest.raises(KeyError):
            router.submit(_frame(0), stream_id="never-opened")


# ------------------------------------------------------ failure containment
def test_worker_kill_quarantines_exactly_victim_streams():
    """Killing one worker resets exactly its streams (cold restart on the
    survivor); surviving streams keep their carry objects untouched."""
    n_streams = 6
    frames = [_frame(100 + s) for s in range(n_streams)]
    with _fleet(n_workers=2) as router:
        pins = {s: router.open_stream(s, alpha=ALPHA) for s in range(n_streams)}
        # warm every stream: two rounds so every carry is non-None
        for _ in range(2):
            for f in [router.submit(frames[s], stream_id=s)
                      for s in range(n_streams)]:
                f.result()
        victim_wid = pins[0]
        survivor = next(w for w in router.workers if w.wid != victim_wid)
        victims = sorted(s for s, w in pins.items() if w == victim_wid)
        keepers = sorted(s for s, w in pins.items() if w != victim_wid)
        assert victims and keepers, "rendezvous split both ways"
        kept_carries = {
            s: survivor.packer.sessions[s].carry for s in keepers
        }
        assert all(c is not None for c in kept_carries.values())

        moved = router.fail_worker(victim_wid)

        # exactly the victim's streams moved, each preceded by a quarantine
        assert sorted(s for s, _ in moved) == victims
        assert router.quarantined_streams == len(victims)
        assert router.rebalanced_streams == len(victims)
        assert sorted(s for s, _, _ in router.rebalance_log) == victims
        for s, old, new in router.rebalance_log:
            assert old == victim_wid and new == survivor.wid
        # victims restart cold on the survivor...
        for s in victims:
            assert router.stream_worker(s) == survivor.wid
            assert survivor.packer.sessions[s].carry is None
        # ...while survivors' carries are the very same objects
        for s in keepers:
            assert survivor.packer.sessions[s].carry is kept_carries[s]
        # the fleet still serves every stream
        for f in [router.submit(frames[s], stream_id=s)
                  for s in range(n_streams)]:
            assert np.isfinite(np.asarray(f.result())).all()
        assert router.workers_alive == 1
        # idempotent: a second failure report is a no-op
        assert router.fail_worker(victim_wid) == []
        assert router.workers_lost == 1


def test_submit_path_detects_dead_worker_and_fails_over():
    """A worker killed WITHOUT telling the router (chaos hook) is noticed
    by the next submit, evacuated, and the frame retried on the survivor."""
    with _fleet(n_workers=2) as router:
        pins = {s: router.open_stream(s, alpha=ALPHA) for s in range(4)}
        for f in [router.submit(_frame(s), stream_id=s) for s in range(4)]:
            f.result()
        victim_wid = pins[0]
        router.kill_worker(victim_wid)  # router not told
        # submits to the dead pin fail over transparently
        for s in range(4):
            assert np.isfinite(
                np.asarray(router.submit(_frame(s), stream_id=s).result())
            ).all()
        assert router.is_dead(victim_wid)
        assert router.workers_lost == 1
        survivor_wid = next(
            w.wid for w in router.workers if w.wid != victim_wid
        )
        assert all(
            router.stream_worker(s) == survivor_wid for s in range(4)
        )


def test_watchdog_detects_silent_death():
    """The watchdog's poll (run synchronously here) notices a dead worker
    with no traffic flowing and triggers the same evacuation."""
    with _fleet(n_workers=2) as router:
        pins = {s: router.open_stream(s, alpha=ALPHA) for s in range(4)}
        for f in [router.submit(_frame(s), stream_id=s) for s in range(4)]:
            f.result()
        victim_wid = pins[0]
        router.kill_worker(victim_wid)
        dog = FleetWatchdog(router, interval_s=60.0)  # won't tick on its own
        try:
            dog.poll()
        finally:
            dog.stop()
        assert router.is_dead(victim_wid)
        assert sorted(s for s, _, _ in router.rebalance_log) == sorted(
            s for s, w in pins.items() if w == victim_wid
        )


# --------------------------------------------------------- plan distribution
def test_mixed_plan_hash_rejected_at_construction():
    ctrl_a = _controller()
    ctrl_b = PlanController(
        cfg=BGConfig(r=8, sigma_s=4.0, sigma_r=60.0), height=H, width=W,
        streams_per_worker=4, temporal=True, sharded=False,
    )
    assert ctrl_a.plan_hash != ctrl_b.plan_hash
    w0 = LocalWorker(0, ctrl_a.payload())
    w1 = LocalWorker(1, ctrl_b.payload())
    try:
        with pytest.raises(PlanMismatch, match="mixed-plan"):
            FleetRouter(workers=[w0, w1], health_interval_s=None)
        # the controller's own verify refuses foreign workers too
        with pytest.raises(PlanMismatch):
            ctrl_a.verify([w0, w1])
    finally:
        w0.close(timeout=5.0)
        w1.close(timeout=5.0)


def test_worker_refuses_tampered_payload():
    payload = _controller().payload()
    payload["plan_hash"] = "0" * 16
    with pytest.raises(PlanMismatch, match="rebuilt plan hashes"):
        LocalWorker(0, payload)


def test_workers_share_one_compiled_executable():
    """Equal plans rebuilt from one payload share the jitted callable —
    plan distribution costs one compile, not N."""
    with _fleet(n_workers=3) as router:
        w0, w1, w2 = router.workers
        assert w0.plan == w1.plan == w2.plan
        assert w0.plan.executable() is w1.plan.executable()
        assert w1.plan.executable() is w2.plan.executable()


# ----------------------------------------- snapshot-restore failover (PR 9)
class _SnapshotTamperer(LocalWorker):
    """A snapshot-enabled LocalWorker whose snapshots can be doctored —
    the deterministic lever for the router's reject paths (staleness,
    foreign hash, poisoned carry) without a subprocess or a clock."""

    def __init__(self, *args, age_offset=0.0, hash_override=None,
                 poison=False, **kw):
        kw.setdefault("snapshots", True)
        super().__init__(*args, **kw)
        self.age_offset = age_offset
        self.hash_override = hash_override
        self.poison = poison

    def carry_snapshot(self, sid):
        import dataclasses

        snap = super().carry_snapshot(sid)
        if snap is None:
            return None
        if self.hash_override is not None:
            snap = dataclasses.replace(snap, plan_hash=self.hash_override)
        if self.poison:
            snap = dataclasses.replace(
                snap, carry=np.full_like(snap.carry, np.nan)
            )
        return dataclasses.replace(
            snap, taken_at=snap.taken_at - self.age_offset
        )


def _warmed_snapshot_fleet(worker_cls=None, n_streams=6, router_kw=None,
                           **worker_extra):
    """Two snapshot-enabled workers (worker 0 optionally a tamperer), all
    streams warmed twice. Returns (router, pins)."""
    payload = _controller().payload()
    kw = dict(max_batch=8, batch_window_ms=1.0, snapshots=True)
    w0 = (worker_cls or LocalWorker)(0, payload, **kw, **worker_extra)
    w1 = LocalWorker(1, payload, **kw)
    router = FleetRouter(workers=[w0, w1], health_interval_s=None,
                         **(router_kw or {}))
    pins = {s: router.open_stream(s, alpha=ALPHA) for s in range(n_streams)}
    for _ in range(2):
        for f in [router.submit(_frame(200 + s), stream_id=s)
                  for s in range(n_streams)]:
            f.result()
    return router, pins


def test_fail_worker_restores_warm_carries_bit_exact():
    """With snapshots enabled, a worker death restores its warm streams'
    carries bit-for-bit onto the survivor — zero cold quarantines — and
    stays idempotent across the snapshot path."""
    router, pins = _warmed_snapshot_fleet()
    with router:
        victim_wid = pins[0]
        victim = router.workers[victim_wid]
        survivor = next(w for w in router.workers if w.wid != victim_wid)
        victims = sorted(s for s, w in pins.items() if w == victim_wid)
        assert victims, "rendezvous gave worker 0 no streams"
        want = {
            s: np.asarray(victim.packer.sessions[s].carry, np.float32)
            for s in victims
        }
        seen = {s: victim.packer.sessions[s].frames_seen for s in victims}

        moved = router.fail_worker(victim_wid)

        assert sorted(s for s, _ in moved) == victims
        assert router.restores == len(victims)
        assert router.quarantined_streams == 0  # warm restore, not cold
        assert router.rebalanced_streams == len(victims)
        assert len(router.restore_staleness_samples) == len(victims)
        assert all(a < 5.0 for a in router.restore_staleness_samples)
        for s in victims:
            sess = survivor.packer.sessions[s]
            np.testing.assert_array_equal(
                np.asarray(sess.carry, np.float32), want[s]
            )
            assert sess.frames_seen == seen[s]
        # restored streams keep serving — and the EMA continues, so the
        # next frame leaves the carry different from the restored state
        for s in victims:
            assert np.isfinite(np.asarray(
                router.submit(_frame(300 + s), stream_id=s).result()
            )).all()
        st = router.stats()
        assert st.restores == len(victims)
        assert st.quarantined_streams == 0
        assert st.restore_staleness_p99 >= 0.0
        # idempotent: the second report neither re-restores nor re-counts
        assert router.fail_worker(victim_wid) == []
        assert router.restores == len(victims)
        assert router.workers_lost == 1


def test_stale_snapshot_falls_back_to_cold_quarantine():
    """A snapshot older than restore_max_age_s is worse than a cold
    restart: the router must quarantine, not resurrect ancient state."""
    router, pins = _warmed_snapshot_fleet(
        worker_cls=_SnapshotTamperer, age_offset=60.0,
        router_kw=dict(restore_max_age_s=5.0),
    )
    with router:
        victims = sorted(s for s, w in pins.items() if w == 0)
        survivor = router.workers[1]
        router.fail_worker(0)
        assert router.restores == 0
        assert router.quarantined_streams == len(victims)
        for s in victims:
            assert survivor.packer.sessions[s].carry is None  # cold


def test_foreign_hash_snapshot_never_restored():
    """A snapshot stamped with a different plan hash is a carry from a
    different dispatch geometry — restoring it would silently corrupt the
    stream's EMA, so it must fall back to quarantine."""
    router, pins = _warmed_snapshot_fleet(
        worker_cls=_SnapshotTamperer, hash_override="f" * 16,
    )
    with router:
        victims = sorted(s for s, w in pins.items() if w == 0)
        router.fail_worker(0)
        assert router.restores == 0
        assert router.quarantined_streams == len(victims)


def test_failed_restore_is_all_or_nothing():
    """A snapshot that fails validation mid-restore (poisoned NaN carry)
    must leave the survivor's stream exactly as open_stream made it —
    cold, zero frames_seen — never half-restored."""
    router, pins = _warmed_snapshot_fleet(
        worker_cls=_SnapshotTamperer, poison=True,
    )
    with router:
        victims = sorted(s for s, w in pins.items() if w == 0)
        survivor = router.workers[1]
        router.fail_worker(0)
        assert router.restores == 0
        assert router.quarantined_streams == len(victims)
        for s in victims:
            sess = survivor.packer.sessions[s]
            assert sess.carry is None
            assert sess.frames_seen == 0
            assert sess.alpha == ALPHA
        # and the stream still serves (cold restart, finite output)
        for s in victims:
            assert np.isfinite(np.asarray(
                router.submit(_frame(400 + s), stream_id=s).result()
            )).all()


# ---------------------------------------------------------- rolling restart
def test_replace_worker_requires_death_and_matching_identity():
    with _fleet(n_workers=2) as router:
        with pytest.raises(ValueError, match="not dead"):
            router.replace_worker(0)
        with pytest.raises(KeyError):
            router.replace_worker("no-such-worker")
        router.fail_worker(0)
        fresh = router.replace_worker(0)
        assert fresh.wid == 0 and fresh.plan_hash == router.plan_hash
        assert router.worker_restarts == 1
        assert router.workers_alive == 2
        assert not router.is_dead(0)
        assert router.workers[0] is fresh


def test_replace_worker_returns_slot_to_rotation():
    """After replacement, new streams place onto the fresh slot by
    rendezvous; existing pins stay where failover put them."""
    with _fleet(n_workers=2) as router:
        pins = {s: router.open_stream(s, alpha=ALPHA) for s in range(6)}
        for f in [router.submit(_frame(s), stream_id=s) for s in range(6)]:
            f.result()
        router.fail_worker(0)
        router.replace_worker(0)
        # failover pins are sticky: nothing moved back
        for s in range(6):
            assert router.stream_worker(s) == 1
        # but new streams rendezvous over BOTH workers again
        new_pins = {
            s: router.open_stream(s, alpha=ALPHA) for s in range(6, 30)
        }
        assert set(new_pins.values()) == {0, 1}
        for s in list(new_pins) + list(pins):
            assert np.isfinite(np.asarray(
                router.submit(_frame(s), stream_id=s).result()
            )).all()


def test_replace_worker_rejects_wrong_wid_and_foreign_plan():
    payload = _controller().payload()
    with _fleet(n_workers=2) as router:
        router.fail_worker(1)
        wrong_wid = LocalWorker(99, payload)
        try:
            with pytest.raises(ValueError, match="does not match slot"):
                router.replace_worker(1, worker=wrong_wid)
        finally:
            wrong_wid.close(timeout=5.0)
        foreign = LocalWorker(1, PlanController(
            cfg=BGConfig(r=8, sigma_s=4.0, sigma_r=60.0), height=H, width=W,
            streams_per_worker=4, temporal=True, sharded=False,
        ).payload())
        try:
            with pytest.raises(PlanMismatch):
                router.replace_worker(1, worker=foreign)
        finally:
            foreign.close(timeout=5.0)
        assert router.worker_restarts == 0
        assert router.is_dead(1)  # the slot is still replaceable
        router.replace_worker(1)
        assert router.worker_restarts == 1


def test_explicit_workers_router_has_no_factory():
    payload = _controller().payload()
    w0 = LocalWorker(0, payload)
    w1 = LocalWorker(1, payload)
    router = FleetRouter(workers=[w0, w1], health_interval_s=None)
    with router:
        router.fail_worker(0)
        with pytest.raises(ValueError, match="factory"):
            router.replace_worker(0)
        # an explicit same-recipe replacement still works
        w0b = LocalWorker(0, payload)
        assert router.replace_worker(0, worker=w0b) is w0b


def test_controller_bless_roundtrip(tmp_path):
    """bless() writes the fleet's plan into a cache file that a later
    plan_for resolves from (provenance flips to the cache)."""
    path = str(tmp_path / "blessed.json")
    ctrl = _controller(streams_per_worker=4)
    key = ctrl.bless(path, measured_us=123.0)
    pc = PlanCache(path)
    ent = pc.lookup(key)
    assert ent is not None and ent["source"] == "controller"
    assert ent["plan_hash"] == ctrl.plan_hash
    resolved = plan_for(
        CFG, H, W, n_frames=4, temporal=True, sharded=False, cache=pc
    )
    assert resolved.plan_hash() == ctrl.plan_hash
    assert resolved.provenance.startswith("cache")


# ------------------------------------------------------------- backpressure
def test_router_sheds_before_worker_queue_overflows():
    """With a wedged worker, the router sheds at its own (small) bound with
    structured FleetSaturated; the worker's far larger request queue never
    fills, so submit can never wedge or raise raw queue.Full."""
    engine_max_queue = 64
    bound = 4
    inj = FaultInjector(FaultPlan(faults=(
        Fault(kind="hang_completion", delay_s=0.25, times=None),
    )))
    router = FleetRouter(
        controller=_controller(streams_per_worker=1),
        n_workers=1,
        max_worker_queue=bound,
        health_interval_s=None,
        worker_kwargs=dict(
            max_batch=1,
            batch_window_ms=0.0,
            max_queue=engine_max_queue,
            fault_injector=inj,
            engine_kwargs=dict(max_inflight=1),
        ),
    )
    try:
        router.open_stream(0, alpha=ALPHA)
        worker = router.workers[0]
        frame = _frame(7)
        accepted, shed = [], 0
        for _ in range(5 * bound):
            try:
                accepted.append(router.submit(frame, stream_id=0, block=False))
            except FleetSaturated as exc:
                shed += 1
                assert exc.wid == worker.wid
                assert exc.limit == bound and exc.depth >= bound
            # the worker's own queue stays far from its capacity: the
            # router's bound fired first every time
            assert worker.queue_depth() <= bound + 1 < engine_max_queue
        assert shed > 0 and router.router_shed == shed
        assert len(accepted) >= bound  # the bound's worth was accepted
        assert router.stats().router_shed == shed
        for f in accepted:
            assert np.isfinite(np.asarray(f.result(timeout=30.0))).all()
    finally:
        router.close()


# ---------------------------------------------------------------- telemetry
def test_engine_stats_merge_exact_percentiles():
    """Fleet percentiles come from the union of the latency reservoirs —
    exactly the single-engine estimator applied to the concatenation, not
    an average of per-engine percentiles."""
    a = EngineStats(
        submitted=10, completed=10, dispatches=5, queue_depth=1,
        inflight_depth=0, deadline_misses=1, mean_batch=2.0,
        latency_ms_p50=2.0, latency_ms_p99=4.0, host_casts=4,
        latency_samples=(1.0, 2.0, 3.0, 4.0),
    )
    b = EngineStats(
        submitted=6, completed=6, dispatches=3, queue_depth=0,
        inflight_depth=2, deadline_misses=0, mean_batch=1.0,
        latency_ms_p50=100.0, latency_ms_p99=400.0, failed=2,
        carry_resets=3, host_casts=1, latency_samples=(100.0, 200.0, 400.0),
    )
    m = EngineStats.merge([a, b, None])
    union = sorted(a.latency_samples + b.latency_samples)
    assert m.latency_samples == tuple(union)
    # same estimator as EngineStats.stats(): samples[min(int(q*n), n-1)]
    n = len(union)
    assert m.latency_ms_p50 == union[min(int(0.50 * n), n - 1)]
    assert m.latency_ms_p99 == union[min(int(0.99 * n), n - 1)]
    # the tail is dominated by the sick engine — never averaged away
    assert m.latency_ms_p99 == 400.0
    assert m.submitted == 16 and m.completed == 16 and m.failed == 2
    assert m.dispatches == 8 and m.deadline_misses == 1
    assert m.carry_resets == 3
    assert m.host_casts == 5 and m.as_dict()["host_casts"] == 5
    # a worker that predates the field reports no host_casts key
    old = a.as_dict()
    del old["host_casts"]
    assert EngineStats(**old).host_casts == 0
    assert m.mean_batch == pytest.approx((2.0 * 5 + 1.0 * 3) / 8)
    # empty and sample-free fallbacks
    empty = EngineStats.merge([])
    assert empty.completed == 0 and empty.latency_ms_p99 == 0.0
    bare = EngineStats.merge([
        EngineStats(4, 4, 2, 0, 0, 0, 2.0, 10.0, 20.0),
        EngineStats(12, 12, 6, 0, 0, 0, 2.0, 30.0, 40.0),
    ])
    assert bare.latency_ms_p50 == pytest.approx((10 * 4 + 30 * 12) / 16)
    assert bare.latency_ms_p99 == pytest.approx((20 * 4 + 40 * 12) / 16)


def test_fleet_stats_rollup():
    with _fleet(n_workers=2) as router:
        for s in range(4):
            router.open_stream(s, alpha=ALPHA)
        for _ in range(3):
            for f in [router.submit(_frame(s), stream_id=s)
                      for s in range(4)]:
                f.result()
        st = router.stats()
        assert st.workers == 2 and st.workers_alive == 2
        assert st.streams == 4 and st.plan_hash == router.plan_hash
        assert st.merged.completed == 12
        assert st.merged.completed == sum(
            p.completed for p in st.per_worker
        )
        assert st.deadline_miss_rate == 0.0
        d = st.as_dict()
        assert d["merged_completed"] == 12
        assert "merged_latency_samples" not in d
        assert d["max_queue_depth"] == max(st.queue_depths)
