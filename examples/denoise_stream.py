"""Streaming denoising — the FPGA macro-pipeline in action, batched and
sharded.

Processes a batch of frames through the fused Pallas macro-pipeline in a
single dispatch (the (batch, stripe) grid: working set O(grid planes + r
lines) per frame, constants shared across frames), then verifies every frame
against the whole-frame path and reports the frames/sec win over looping the
single-frame kernel. On a multi-device host the batch axis is additionally
sharded over a 1-D device mesh (collective-free data parallelism — the
service path). This is the paper's real-time video use case scaled to
multi-frame throughput.

Run:  PYTHONPATH=src python examples/denoise_stream.py
      # multi-device scale-out on a CPU host:
      XLA_FLAGS=--xla_force_host_platform_device_count=4 \
          PYTHONPATH=src python examples/denoise_stream.py
"""
import time

import jax
import jax.numpy as jnp

from repro.core import (
    BGConfig,
    add_gaussian_noise,
    bilateral_grid_filter,
    grid_shape,
    mssim,
    synthetic_batch,
)
from repro.plan import plan_for


def main():
    h, w, n_frames = 270, 480, 4
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    gx, gy, gz = grid_shape(h, w, cfg)
    working = (3 * gy * gz * 2 + 2 * gy * gz + 3 * cfg.r * w) * 4
    print(f"frame {h}x{w}: grid {gx}x{gy}x{gz}, per-frame working set "
          f"~{working/1024:.0f} KiB vs {h*w*4/1024:.0f} KiB per frame")

    clean = synthetic_batch(n_frames, h, w, seed=0)
    noisy = add_gaussian_noise(clean, 30.0, seed=100)

    # one compiled plan for the whole run: the plan layer picks the backend
    # and auto-tunes the fused-kernel batch tile from the frame geometry
    # (sharded=False here so the single-device/sharded comparison below is
    # explicit; sharding is its own plan further down)
    plan = plan_for(cfg, h, w, n_frames=n_frames, sharded=False)
    print(f"plan: backend={plan.backend} batch_tile={plan.batch_tile}")

    # batched fused path: all frames in one dispatch
    out_b = plan(noisy)
    jax.block_until_ready(out_b)  # warm-up/compile
    t0 = time.perf_counter()
    out_b = plan(noisy)
    jax.block_until_ready(out_b)
    dt_batch = time.perf_counter() - t0

    # looped single-frame baseline: the same plan, one frame per dispatch
    for i in range(n_frames):
        jax.block_until_ready(plan(noisy[i]))
    t0 = time.perf_counter()
    out_loop = []
    for i in range(n_frames):
        out_loop.append(plan(noisy[i]))
    jax.block_until_ready(out_loop)
    dt_loop = time.perf_counter() - t0

    for i in range(n_frames):
        ref = bilateral_grid_filter(noisy[i], cfg)
        diff = float(jnp.max(jnp.abs(out_b[i] - ref)))
        print(f"frame {i}: MSSIM {float(mssim(clean[i], out_b[i])):.4f}  "
              f"|batched-whole_frame|max={diff:.1e}")

    fps_b = n_frames / dt_batch
    fps_l = n_frames / dt_loop
    print(f"batched: {dt_batch*1e3/n_frames:6.1f} ms/frame ({fps_b:.1f} fps)  "
          f"looped: {dt_loop*1e3/n_frames:6.1f} ms/frame ({fps_l:.1f} fps)  "
          f"speedup {fps_b/fps_l:.2f}x "
          f"(interpret mode off-TPU; dispatch amortization shows at smaller "
          f"frames — see benchmarks/bench_bg_throughput.py)")

    # sharded service path: batch axis over a 1-D device mesh, no collectives
    nd = jax.device_count()
    if nd > 1:
        shard_plan = plan_for(cfg, h, w, n_frames=n_frames)  # auto-meshes
        out_s = shard_plan(noisy)
        jax.block_until_ready(out_s)  # warm-up/compile
        t0 = time.perf_counter()
        out_s = shard_plan(noisy)
        jax.block_until_ready(out_s)
        dt_shard = time.perf_counter() - t0
        same = bool(jnp.all(out_s == out_b))
        print(f"sharded over {nd} devices: {dt_shard*1e3/n_frames:6.1f} ms/frame "
              f"({n_frames/dt_shard:.1f} fps)  bit-identical to batched: {same}")
    else:
        print("single device: sharded path == batched path (run with "
              "XLA_FLAGS=--xla_force_host_platform_device_count=4 to see the "
              "mesh dispatch)")


if __name__ == "__main__":
    main()
