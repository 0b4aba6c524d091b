"""Serving launcher: load (or init) params, run batched requests through the
continuous-batching engine — or serve denoise frames through the sharded
bilateral-grid frame engine — or serve multi-stream video through the async
engine + temporal grid (``--workers N`` fronts the streams with a
``repro.fleet.FleetRouter`` over N workers instead of one bare engine).

    python -m repro.launch.serve --arch yi-6b --smoke --requests 8
    python -m repro.launch.serve --frames 32 --frame-hw 96x128
    python -m repro.launch.serve --video 4 --video-frames 24 --fps 30 \\
        --alpha 0.6 --deadline-ms 100
    python -m repro.launch.serve --video 8 --workers 3 --alpha 0.6
"""
from __future__ import annotations

import argparse
import time


def serve_frames(args) -> None:
    """Frame-denoise service smoke: stream synthetic noisy frames through the
    mesh-divisible micro-batching engine (sharded over all local devices)."""
    import jax

    from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
    from repro.plan import plan_for
    from repro.serving import FrameDenoiseEngine, FrameRequest

    h, w = (int(x) for x in args.frame_hw.split("x"))
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    # the plan layer auto-tunes batch_tile (VMEM-budget model) and
    # stream_input (forced on by --stream-input, else geometry-selected)
    plan = plan_for(
        cfg, h, w, stream_input=True if args.stream_input else None
    )
    eng = FrameDenoiseEngine(plan=plan, max_batch=args.micro_batch)
    print(
        f"[serve] frame engine: {jax.device_count()} device(s), "
        f"micro-batch {eng.max_batch} (mesh-divisible by {eng.n_devices}), "
        f"plan[{plan.describe()}]"
    )
    clean = synthetic_batch(args.frames, h, w, seed=0)
    noisy = add_gaussian_noise(clean, 30.0, seed=1)

    # Warm-up compile on the batch shapes the timed loop will actually
    # dispatch: frames arrive one per step(), so steady-state dispatches are
    # n_devices-sized, plus the forced ragged tail.
    warm_sizes = {min(eng.n_devices, args.frames)}
    if args.frames % eng.n_devices:
        warm_sizes.add(args.frames % eng.n_devices)
    for size in sorted(warm_sizes):
        for i in range(size):
            eng.submit(FrameRequest(uid=-1 - i, frame=noisy[i % args.frames]))
        eng.flush()

    t0 = time.monotonic()
    done = []
    for i in range(args.frames):
        eng.submit(FrameRequest(uid=i, frame=noisy[i]))
        # dispatches whenever a device-count multiple is queued
        done.extend(eng.step())
    done.extend(eng.flush())  # ragged tail
    jax.block_until_ready([r.result for r in done])
    dt = time.monotonic() - t0
    assert len(done) == args.frames and all(r.result is not None for r in done)
    print(
        f"[serve] {args.frames} frames {h}x{w} in {dt:.2f}s "
        f"({args.frames / dt:.1f} frames/s)"
    )


def serve_fleet(args) -> None:
    """Multi-worker video service smoke: the same N-stream synthetic traffic
    as ``serve_video``, fronted by a ``repro.fleet.FleetRouter`` over
    ``--workers`` engines — thread-hosted by default,
    ``--worker-backend subprocess`` for process-isolated workers (one
    engine process each behind the socket codec, with heartbeats and
    warm-carry snapshot failover). One controller-resolved plan for the
    whole fleet, sticky stream affinity, fleet-level admission and
    backpressure. Prints fleet throughput + the exactly-merged latency
    tail (``FleetStats``)."""
    import jax
    import numpy as np

    from repro.core import BGConfig, add_gaussian_noise
    from repro.data import synthetic_video
    from repro.fleet import FleetRouter, PlanController

    h, w = (int(x) for x in args.frame_hw.split("x"))
    n_streams, n_frames = args.video, args.video_frames
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    controller = PlanController(
        cfg=cfg,
        height=h,
        width=w,
        streams_per_worker=max(1, -(-n_streams // args.workers)),
        temporal=True,
    )
    backend = getattr(args, "worker_backend", "local")
    print(
        f"[serve] fleet: {args.workers} {backend} worker(s) x "
        f"{jax.device_count()} "
        f"device(s), {n_streams} stream(s) x {n_frames} frames {h}x{w}, "
        f"alpha={args.alpha:g}, plan[{controller.plan.describe()}] "
        f"hash={controller.plan_hash}"
    )
    traffic = []
    for s in range(n_streams):
        vid = synthetic_video(s, n_frames, h, w, motion=1.5)
        traffic.append(
            [np.asarray(add_gaussian_noise(vid[t], 30.0, seed=1000 * s + t))
             for t in range(n_frames)]
        )
    router = FleetRouter(
        controller=controller,
        n_workers=args.workers,
        worker_backend=backend,
        worker_kwargs=dict(
            max_batch=max(1, -(-n_streams // args.workers)),
            batch_window_ms=args.batch_window_ms,
        ),
    )
    deadline = args.deadline_ms if args.deadline_ms > 0 else None
    period = 0.0 if not args.fps else 1.0 / args.fps
    try:
        for s in range(n_streams):
            wid = router.open_stream(s, alpha=args.alpha)
            print(f"[serve]   stream {s} -> worker {wid} (sticky)")
        # warm-up outside the timed window: per-worker pack-shape compiles
        # + first-frame EMA warm-up
        for f in [router.submit(traffic[s][0], stream_id=s)
                  for s in range(n_streams)]:
            f.result()
        router.flush()
        t0 = time.monotonic()
        futs = []
        for t in range(n_frames):
            if period:
                pause = t0 + t * period - time.monotonic()
                if pause > 0:
                    time.sleep(pause)
            for s in range(n_streams):
                futs.append(
                    router.submit(
                        traffic[s][t], stream_id=s, deadline_ms=deadline
                    )
                )
        for f in futs:
            f.result()
        dt = time.monotonic() - t0
        st = router.stats()
    finally:
        router.close()
    total = n_streams * n_frames
    m = st.merged
    print(
        f"[serve] {total} frames in {dt:.2f}s ({total / dt:.1f} frames/s, "
        f"{total / dt / n_streams:.1f} fps/stream) over "
        f"{st.workers_alive}/{st.workers} workers  "
        f"p50={m.latency_ms_p50:.1f}ms p99={m.latency_ms_p99:.1f}ms "
        f"(merged reservoirs)  dispatches={m.dispatches} "
        f"mean_batch={m.mean_batch:.1f}  "
        f"deadline_miss_rate={st.deadline_miss_rate:.4f} "
        f"shed={st.router_shed}"
    )


def serve_video(args) -> None:
    """Multi-stream video service smoke: N synthetic streams submit frames at
    a target per-stream fps into the async engine (fused in-kernel temporal
    grid-EMA per stream when --alpha > 0 — one kernel dispatch per pack,
    warm and cold streams mixed, stream axis sharded over the local mesh);
    prints sustained throughput + latency tail."""
    import jax
    import numpy as np

    from repro.core import BGConfig, add_gaussian_noise
    from repro.data import synthetic_video
    from repro.plan import plan_for
    from repro.serving import AsyncFrameEngine
    from repro.video import MultiStreamPacker

    h, w = (int(x) for x in args.frame_hw.split("x"))
    n_streams, n_frames = args.video, args.video_frames
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    print(
        f"[serve] video: {n_streams} stream(s) x {n_frames} frames {h}x{w}, "
        f"alpha={args.alpha:g}, target {args.fps or 'max'} fps/stream, "
        f"{jax.device_count()} device(s)"
    )
    traffic = []
    for s in range(n_streams):
        vid = synthetic_video(s, n_frames, h, w, motion=1.5)
        traffic.append(
            [np.asarray(add_gaussian_noise(vid[t], 30.0, seed=1000 * s + t))
             for t in range(n_frames)]
        )

    # One plan for the whole service: plan_for auto-tunes the fused-kernel
    # batch tile from the pack geometry (whole pack in one macro-pipeline
    # sweep while it fits the VMEM-budget model) — nothing threads
    # batch_tile= by hand anymore; the packer asks the plan for its tile.
    # Always temporal-capable: the packer serves whatever warm/cold mix the
    # streams produce, so the plan must never be the input-streamed backend
    # (which cannot carry the grid EMA; the packer rejects it).
    plan = plan_for(cfg, h, w, n_frames=n_streams, temporal=True)
    # describe() includes provenance: whether the measured plan cache, the
    # roofline model, or a pinned kwarg chose this dispatch geometry
    print(f"[serve] plan[{plan.describe()}]")

    # warm-up compile on the steady-state pack shape through a throwaway
    # engine: the jit caches are global, but the serving engine's telemetry
    # (p99 must not report compile time) and the temporal stream state
    # (frame 0 must enter each EMA exactly once) start clean.
    warm_packer = MultiStreamPacker(plan=plan)
    for s in range(n_streams):
        warm_packer.open(s, alpha=args.alpha)
    with AsyncFrameEngine(cfg, max_batch=n_streams, packer=warm_packer) as warm:
        for f in [warm.submit(traffic[s][0], stream_id=s) for s in range(n_streams)]:
            f.result()

    packer = MultiStreamPacker(plan=plan)
    for s in range(n_streams):
        packer.open(s, alpha=args.alpha)
    eng = AsyncFrameEngine(
        cfg,
        max_batch=n_streams,
        batch_window_ms=args.batch_window_ms,
        packer=packer,
    )
    period = 0.0 if not args.fps else 1.0 / args.fps
    deadline = args.deadline_ms if args.deadline_ms > 0 else None
    t0 = time.monotonic()
    futs = []
    for t in range(n_frames):
        if period:
            pause = t0 + t * period - time.monotonic()
            if pause > 0:
                time.sleep(pause)
        for s in range(n_streams):
            futs.append(
                eng.submit(traffic[s][t], stream_id=s, deadline_ms=deadline)
            )
    for f in futs:
        f.result()
    dt = time.monotonic() - t0
    st = eng.stats()
    eng.close()
    total = n_streams * n_frames
    print(
        f"[serve] {total} frames in {dt:.2f}s ({total / dt:.1f} frames/s, "
        f"{total / dt / n_streams:.1f} fps/stream)  "
        f"p50={st.latency_ms_p50:.1f}ms p99={st.latency_ms_p99:.1f}ms  "
        f"dispatches={st.dispatches} mean_batch={st.mean_batch:.1f}  "
        f"deadline_misses={st.deadline_misses}"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="LM arch (omit with --frames)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument(
        "--frames",
        type=int,
        default=0,
        help="serve N synthetic denoise frames through the sharded BG frame "
        "engine instead of LM requests",
    )
    ap.add_argument("--frame-hw", default="96x128", help="frame size HxW")
    ap.add_argument("--micro-batch", type=int, default=16)
    ap.add_argument(
        "--stream-input",
        action="store_true",
        help="double-buffered HBM->VMEM input DMA in the fused kernel",
    )
    ap.add_argument(
        "--video",
        type=int,
        default=0,
        help="serve N concurrent synthetic video streams through the async "
        "engine + temporal bilateral grid instead of LM requests",
    )
    ap.add_argument(
        "--video-frames", type=int, default=24, help="frames per video stream"
    )
    ap.add_argument(
        "--workers",
        type=int,
        default=0,
        help="with --video: front the streams with a fleet router over N "
        "workers (one controller-distributed plan, sticky stream affinity) "
        "instead of a single engine",
    )
    ap.add_argument(
        "--worker-backend",
        choices=("local", "subprocess"),
        default="local",
        help="with --workers: host each worker's engine in the router's "
        "process (local, thread-hosted) or in its own process behind the "
        "socket codec (subprocess: crash isolation, heartbeat liveness, "
        "warm-carry snapshot failover)",
    )
    ap.add_argument(
        "--fps",
        type=float,
        default=0.0,
        help="target per-stream frame rate (0 = submit at max rate)",
    )
    ap.add_argument(
        "--alpha",
        type=float,
        default=0.6,
        help="temporal grid EMA weight per stream (0 = per-frame path)",
    )
    ap.add_argument(
        "--deadline-ms",
        type=float,
        default=0.0,
        help="per-frame latency budget; expiring deadlines force early "
        "micro-batch dispatch (0 = none)",
    )
    ap.add_argument(
        "--batch-window-ms",
        type=float,
        default=5.0,
        help="async micro-batch accumulation window",
    )
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.video:
        if args.workers:
            serve_fleet(args)
        else:
            serve_video(args)
        return
    if args.frames:
        serve_frames(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --frames or --video is given")

    import jax

    from repro.configs.registry import get_config, get_smoke_config
    from repro.models import init_params
    from repro.serving import Request, ServeEngine

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.ckpt_dir)
        like = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
        state, meta = mgr.restore({"params": like})
        params = state["params"]
        print(f"[serve] restored step {meta['step']}")
    else:
        params = init_params(jax.random.PRNGKey(0), cfg)
        print("[serve] random params (demo)")

    eng = ServeEngine(cfg, params, max_slots=args.slots, max_len=args.max_len)
    pending = [
        Request(uid=i, prompt=[(7 * i + j) % cfg.vocab_size for j in range(4 + i % 5)],
                max_tokens=args.max_tokens)
        for i in range(args.requests)
    ]
    t0 = time.monotonic()
    done = 0
    queue = list(pending)
    while done < len(pending):
        while queue and eng.submit(queue[0]):
            queue.pop(0)
        done += len(eng.step())
    dt = time.monotonic() - t0
    toks = sum(len(r.generated) for r in pending)
    print(f"[serve] {len(pending)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s)")
    for r in pending[:4]:
        print(f"  uid={r.uid} prompt={r.prompt} -> {r.generated}")


if __name__ == "__main__":
    main()
