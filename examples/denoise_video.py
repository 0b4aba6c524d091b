"""Real-time video denoising — temporal bilateral grid + async multi-stream
serving.

Three acts:

  1. Temporal grid on a static scene: sweep the EMA weight `a` and show the
     denoised-vs-clean PSNR climbing as the grid accumulates history across
     frames (the anti-flicker effect, measurable as noise suppression).
     Every alpha rides the fused kernel: the EMA blends the blurred grid
     planes in VMEM inside the GC||GF||TI macro-pipeline.
  2. a == 0 degenerates to the per-frame fused path, bit-identically — the
     temporal extension costs nothing when it is switched off.
  3. Multi-stream async serving: N panning streams submit frames to the
     AsyncFrameEngine (futures + deadline-aware micro-batching + double-
     buffered host->device feeding); per-stream grids are carried in one
     stacked array and the whole pack — warm and cold streams alike — is a
     single fused-kernel dispatch per round.

Run:  PYTHONPATH=src python examples/denoise_video.py
"""
import time

import numpy as np

from repro.core import BGConfig, add_gaussian_noise, psnr
from repro.data import synthetic_video
from repro.plan import BGPlan, plan_for
from repro.serving import AsyncFrameEngine
from repro.video import MultiStreamPacker, temporal_denoise

N_FRAMES = 10
H, W = 96, 128


def main():
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    fused = BGPlan(cfg=cfg, backend="fused")

    # ---- 1. temporal accumulation on a static scene --------------------
    clean = synthetic_video(0, 1, H, W, motion=0.0)[0]
    noisy = [
        np.asarray(add_gaussian_noise(clean, 30.0, seed=t)) for t in range(N_FRAMES)
    ]
    print(f"static {H}x{W} scene, sigma=30 noise, {N_FRAMES} frames:")
    print(f"  noisy input:            psnr {float(psnr(clean, noisy[-1])):6.2f} dB")
    for alpha in (0.0, 0.3, 0.6, 0.8):
        packer = MultiStreamPacker(fused)
        packer.open("cam", alpha=alpha)
        for t in range(N_FRAMES):
            out = packer.pack({"cam": noisy[t]})["cam"]
        print(
            f"  alpha={alpha:<4g} last frame:  psnr {float(psnr(clean, out)):6.2f} dB"
        )

    # ---- 2. a == 0 is the per-frame fused path, bit-identical ----------
    frame = noisy[0]
    out_t, carry = temporal_denoise(frame, plan=fused, alpha=0.0)
    ref = fused(frame)
    assert carry is None and bool(np.all(np.asarray(out_t) == np.asarray(ref)))
    print("alpha=0 output bit-identical to the per-frame fused path: True")

    # ---- 3. async multi-stream serving ---------------------------------
    n_streams = 4
    traffic = []
    for s in range(n_streams):
        vid = synthetic_video(s, N_FRAMES, H, W, motion=1.5)
        traffic.append(
            [np.asarray(add_gaussian_noise(vid[t], 30.0, seed=99 * s + t))
             for t in range(N_FRAMES)]
        )

    # plan-driven dispatch: plan_for auto-tunes the fused-kernel batch tile
    # for the pack geometry; the packer asks the plan for its tile
    video_plan = plan_for(cfg, H, W, n_frames=n_streams, temporal=True)
    print(f"video plan: backend={video_plan.backend} "
          f"batch_tile={video_plan.batch_tile}")

    def fresh_packer():
        p = MultiStreamPacker(plan=video_plan)
        for s in range(n_streams):
            p.open(s, alpha=0.6)
        return p

    # warm-up compile through a throwaway engine so the timed engine's
    # latency telemetry and temporal stream state start clean
    with AsyncFrameEngine(packer=fresh_packer(), max_batch=n_streams) as warm:
        for s in range(n_streams):
            warm.submit(traffic[s][0], stream_id=s)
        warm.flush()

    with AsyncFrameEngine(
        packer=fresh_packer(), max_batch=n_streams, batch_window_ms=20.0
    ) as eng:
        t0 = time.perf_counter()
        futs = [
            eng.submit(traffic[s][t], stream_id=s, deadline_ms=500.0)
            for t in range(N_FRAMES)
            for s in range(n_streams)
        ]
        outs = [f.result() for f in futs]
        dt = time.perf_counter() - t0
        st = eng.stats()
    total = len(outs)
    print(
        f"async: {n_streams} streams, {total} frames in {dt * 1e3:.0f}ms "
        f"({total / dt:.0f} frames/s) — p50={st.latency_ms_p50:.1f}ms "
        f"p99={st.latency_ms_p99:.1f}ms mean_batch={st.mean_batch:.1f} "
        f"deadline_misses={st.deadline_misses}"
    )


if __name__ == "__main__":
    main()
