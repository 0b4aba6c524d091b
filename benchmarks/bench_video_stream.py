"""Multi-stream video serving: the async engine's sustained frames/sec.

The deployment shape for the paper's real-time denoiser is N concurrent
video streams, each delivering frames that must come back denoised — so the
figure of merit is sustained service throughput plus the request-latency
tail, not single-dispatch time. This bench drives frame traffic
(round-robin over N streams) through ``AsyncFrameEngine``: bounded-queue
submission with futures; the dispatch thread stacks/transfers batch N+1
while batch N computes and the completion thread realizes batch N-1
(double-buffered feeding), so host-side work hides behind device compute.

Every result is realized to host memory (a service must) and checked
against the plan's own answer on the stacked frames. The engine reports
p50/p99 request latency from its telemetry, and the end-of-run ``stats()``
dict is exported as ``bg_video/stats_*`` rows so the serving telemetry
lands in the ``BENCH_<ts>.json`` perf trajectory instead of evaporating
with the process.

The ``ratio/bg_temporal_fused_vs_staged`` row gates the PR-4 warm path: one
warm multi-stream pack dispatched through the fused temporal kernel (the
in-VMEM grid EMA, one kernel for GC||GF||EMA||TI) must beat the same pack
through the staged jnp oracle (``grid_create -> grid_blur -> EMA -> slice``,
grid round-tripping between stages) by the declared floor. Both sides run
``temporal_denoise`` on identical frames/carries/alphas in the same
process, so the ratio is a property of the code paths, not the host
(floor 2.0; measured ~2.4-3x in interpret mode at the gate shape below).
"""
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import BGConfig, add_gaussian_noise
from repro.data import synthetic_video
from repro.plan import BGPlan
from repro.serving import AsyncFrameEngine, EngineStats
from repro.video import MultiStreamPacker, temporal_denoise

REPS_QUICK, REPS_FULL = 3, 5
TEMPORAL_ALPHA = 0.6
# Fused-temporal >= 2x the staged oracle on the same warm pack is the PR-4
# acceptance floor. Gate shape: a many-stream warm pack (the steady state of
# a loaded video service) at a paper-range window radius, h ragged wrt r so
# both paths sweep the same stripe count, batch_tile=pack so the whole pack
# rides one macro-pipeline sweep. The fused win comes from doing GC/GF/EMA/TI
# in one kernel over a VMEM-resident grid instead of materializing the
# staged pipeline's per-stage grids; measured ~2.4-3x in interpret mode.
TEMPORAL_FUSED_FLOOR = 2.0
TEMPORAL_GATE_HW_R = (60, 96, 16)
TEMPORAL_REPS = 9


def _traffic(n_streams, frames_per_stream, h, w):
    """Round-robin frame traffic: [(stream_id, frame), ...] in arrival order."""
    vids = [
        synthetic_video(s, frames_per_stream, h, w, motion=1.5)
        for s in range(n_streams)
    ]
    arrivals = []
    for t in range(frames_per_stream):
        for s in range(n_streams):
            noisy = add_gaussian_noise(vids[s][t], 30.0, seed=1000 * s + t)
            arrivals.append((s, np.asarray(noisy)))
    return arrivals


def _run_async(arrivals, max_batch, *, plan=None, packer=None):
    eng = AsyncFrameEngine(
        plan=plan, packer=packer, max_batch=max_batch, batch_window_ms=50.0
    )
    t0 = time.perf_counter()
    futs = [
        eng.submit(frame, stream_id=sid if packer is not None else None)
        for sid, frame in arrivals
    ]
    outs = [np.asarray(f.result()) for f in futs]
    dt = time.perf_counter() - t0
    stats = eng.stats()
    eng.close()
    return dt, outs, stats


def run(quick: bool = False):
    # Warm-path gate, window 1 of 2 (window 2 runs after the engine benches;
    # see _temporal_time_window for why the spacing matters)
    gate = _temporal_gate_setup(quick)
    tf, ts = _temporal_time_window(gate)

    h, w, r = (32, 48, 4) if quick else (64, 96, 6)
    n_streams = 4 if quick else 8
    frames_per_stream = 16 if quick else 12
    reps = REPS_QUICK if quick else REPS_FULL
    cfg = BGConfig(r=r, sigma_s=4.0, sigma_r=60.0)
    plan = BGPlan(cfg=cfg)
    arrivals = _traffic(n_streams, frames_per_stream, h, w)
    n = len(arrivals)
    # micro-batch spans two stream rounds: per-dispatch handoff overhead
    # (thread wakeups, queue hops) amortizes over more frames. The temporal
    # pack below is capped at one frame per stream by construction, so it
    # keeps max_batch == n_streams.
    mb = min(2 * n_streams, n)

    # warm-up compiles for the dispatch shapes the engine will hit
    _run_async(arrivals, mb, plan=plan)

    t_async = []
    for _ in range(reps):  # best-of-reps, as in bench_bg_throughput
        dt, outs_async, stats = _run_async(arrivals, mb, plan=plan)
        t_async.append(dt)
    # the oracle: the plan's own answer on the stacked frames
    ref = np.asarray(plan(np.stack([frame for _, frame in arrivals])))
    for i, out in enumerate(outs_async):
        np.testing.assert_array_equal(out, ref[i])

    stats_plain = stats  # last per-frame async engine snapshot (merged below)
    fps_async = n / min(t_async)
    tag = f"s{n_streams}_f{frames_per_stream}_{h}x{w}"
    rows = [
        (
            f"bg_video/async_engine_{tag}",
            min(t_async) / n * 1e6,
            f"fps={fps_async:.0f} p50={stats.latency_ms_p50:.1f}ms "
            f"p99={stats.latency_ms_p99:.1f}ms "
            f"mean_batch={stats.mean_batch:.1f}",
        ),
    ]

    # the temporal multi-stream path (in-kernel fused grid-EMA) through the
    # same async front — the flicker-suppressing video service mode. The
    # packer takes the tuned plan (what the video service does post-PR-5);
    # its describe() string lands in the row so the dispatch geometry and
    # its provenance (cache/model/explicit) are attributable in snapshots.
    from repro.plan import plan_for

    temporal_plan = plan_for(
        cfg, h, w, n_frames=n_streams, temporal=True, sharded=False,
        cache=False,
    )
    packer = MultiStreamPacker(plan=temporal_plan)
    for s in range(n_streams):
        packer.open(s, alpha=TEMPORAL_ALPHA)
    _run_async(arrivals, n_streams, packer=packer)  # warm-up
    dt, _, stats = _run_async(arrivals, n_streams, packer=packer)
    rows.append(
        (
            f"bg_video/async_temporal_a{TEMPORAL_ALPHA:g}_{tag}",
            dt / n * 1e6,
            f"fps={n / dt:.0f} p50={stats.latency_ms_p50:.1f}ms "
            f"p99={stats.latency_ms_p99:.1f}ms (fused in-kernel grid-EMA) "
            f"plan={temporal_plan.describe()}",
        )
    )
    # serving telemetry -> the BENCH_<ts>.json trajectory (the EngineStats
    # snapshot is otherwise transient); values land in the us_per_call
    # column, units per row in the derived string
    stat_values = stats.as_dict()
    for key, unit in (
        ("mean_batch", "frames/dispatch"),
        ("dispatches", "count"),
        ("queue_depth", "requests at drain"),
        ("deadline_misses", "count"),
        ("latency_ms_p50", "ms"),
        ("latency_ms_p99", "ms"),
        # PR-6 reliability counters — all zero on this clean run (the chaos
        # soak exercises them); exported so the snapshot trajectory shows a
        # healthy serve as *measured-zero*, not unknown
        ("failed", "count"),
        ("retries", "count"),
        ("fallbacks", "count"),
        ("carry_resets", "count"),
        ("shed", "count"),
        ("watchdog_trips", "count"),
    ):
        rows.append(
            (
                f"bg_video/stats_{key}_{tag}",
                float(stat_values[key]),
                f"{unit} — async temporal engine telemetry snapshot "
                f"(serving.EngineStats)",
            )
        )
    # cross-engine aggregation through the fleet's exact-merge path: the
    # per-frame and temporal engines' reservoirs concatenate, so the merged
    # percentiles are percentiles of the union — the same EngineStats.merge
    # the FleetRouter's FleetStats rolls N workers up with
    merged = EngineStats.merge([stats_plain, stats])
    for key, unit in (
        ("completed", "count over both engines"),
        ("dispatches", "count over both engines"),
        ("latency_ms_p50", "ms, exact over concatenated reservoirs"),
        ("latency_ms_p99", "ms, exact over concatenated reservoirs"),
    ):
        rows.append(
            (
                f"bg_video/merged_{key}_{tag}",
                float(merged[key]),
                f"{unit} — EngineStats.merge of the per-frame + temporal "
                f"async engines (the fleet aggregation path)",
            )
        )
    # warm-path gate, window 2: per-side minima over both windows
    tf2, ts2 = _temporal_time_window(gate)
    rows.extend(_temporal_rows(gate, tf + tf2, ts + ts2))
    return rows


def _temporal_gate_setup(quick: bool):
    """Fixed inputs + timed closures for the warm-path gate (built once; the
    frames/carries are shared by every timing window).

    Both sides dispatch prebuilt ``BGPlan``s — the post-refactor service
    path — so the row times the two *compiled dispatch paths* (in-kernel
    EMA vs the grid-visible staged pipeline) on identical
    frames/carries/alphas, with no per-call shim or plan-construction cost
    on either side."""
    from repro.plan import BGPlan, plan_for

    h, w, r = TEMPORAL_GATE_HW_R
    n = 64 if quick else 96
    cfg = BGConfig(r=r, sigma_s=4.0, sigma_r=60.0)
    fused_plan = plan_for(
        cfg, h, w, n_frames=n, temporal=True, sharded=False, batch_tile=n
    )
    staged_plan = BGPlan(cfg=cfg, backend="reference", temporal=True)
    vid = synthetic_video(7, n, h, w, motion=1.5)
    # device-resident frames: this row gates the *dispatch* (kernel vs staged
    # pipeline); host->device conversion is identical on both sides and is
    # already measured by the engine-level rows
    frames = jnp.stack(
        [add_gaussian_noise(vid[t], 30.0, seed=t) for t in range(n)]
    ).block_until_ready()
    alpha = jnp.asarray(np.full((n,), TEMPORAL_ALPHA, np.float32))
    # a real warm carry (one fused warm-up step), shared by both sides
    _, carry = temporal_denoise(
        frames, alpha=TEMPORAL_ALPHA, plan=fused_plan
    )

    def fused():
        jax.block_until_ready(fused_plan(frames, carry=carry, alpha=alpha))

    def staged():
        jax.block_until_ready(staged_plan(frames, carry=carry, alpha=alpha))

    return {"n": n, "tag": f"warm{n}_{h}x{w}_r{r}", "hwr": (h, w, r),
            "fused": fused, "staged": staged,
            "fused_desc": fused_plan.describe(),
            "staged_desc": staged_plan.describe()}


def _temporal_time_window(gate, reps=TEMPORAL_REPS):
    """One interleaved best-of-reps timing window; returns (tf, ts) lists.

    Transient host states after heavy load (memory reclaim, turbo/thermal
    decay on small CI boxes) depress the compute-bound fused side much more
    than the gather/scatter-latency-bound staged side, skewing the *ratio*,
    not just the absolute times. The caller therefore times two windows —
    one before and one after the engine benches, tens of seconds apart —
    and the per-side minimum over all windows estimates the true dispatch
    cost (the same best-of principle as the interleaved reps within a
    window)."""
    gc.collect()  # prior benches' garbage must not bill this window
    for _ in range(2):  # re-warm: first executions page code/pools
        gate["fused"]()
        gate["staged"]()
    tf, ts = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        gate["fused"]()
        tf.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        gate["staged"]()
        ts.append(time.perf_counter() - t0)
    return tf, ts


def _temporal_rows(gate, tf, ts):
    n = gate["n"]
    h, w, r = gate["hwr"]
    tag = gate["tag"]
    return [
        (
            f"bg_video/temporal_fused_{tag}",
            min(tf) / n * 1e6,
            f"fps={n / min(tf):.0f} one-kernel in-VMEM grid-EMA warm path "
            f"plan={gate['fused_desc']}",
        ),
        (
            f"bg_video/temporal_staged_{tag}",
            min(ts) / n * 1e6,
            f"fps={n / min(ts):.0f} staged create->blur->EMA->slice oracle "
            f"plan={gate['staged_desc']}",
        ),
        (
            "ratio/bg_temporal_fused_vs_staged",
            min(ts) / min(tf),
            f"floor={TEMPORAL_FUSED_FLOOR} fused-temporal/staged dispatch "
            f"time on one {n}-stream warm pack {h}x{w} r={r} (in-kernel EMA "
            f"vs grid-visible staged pipeline, same frames/carries/alphas; "
            f"min over two timing windows)",
        ),
    ]


