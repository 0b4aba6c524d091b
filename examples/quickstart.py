"""Quickstart: denoise an image with the variable-window bilateral grid.

Reproduces the paper's core comparison on a synthetic scene: noisy input ->
BG-denoised vs exact-BF-denoised, MSSIM against the clean original, plus the
shift-only (pow2) arithmetic mode and the Pallas kernel path.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax.numpy as jnp

from repro.core import (
    BGConfig,
    add_gaussian_noise,
    bilateral_filter,
    bilateral_grid_filter,
    bilateral_grid_filter_fixed,
    mssim,
    psnr,
    synthetic_image,
)
from repro.plan import BGPlan, plan_for


def main():
    h, w = 256, 384
    clean = synthetic_image(h, w)
    noisy = add_gaussian_noise(clean, sigma=30.0)
    cfg = BGConfig(r=7, sigma_s=4.0, sigma_r=50.0)

    # every dispatch decision (backend, batch tile, input streaming, mesh)
    # lives in one compiled plan — see repro.plan
    plan = plan_for(cfg, h, w, n_frames=1)

    results = {
        "noisy input": noisy,
        "exact BF (paper's baseline)": bilateral_filter(noisy, 7, 4.0, 50.0),
        "BG (this paper)": bilateral_grid_filter(noisy, cfg),
        "BG pow2/shift-only": bilateral_grid_filter_fixed(
            noisy, BGConfig(r=7, sigma_s=4.0, sigma_r=50.0, weight_mode="pow2")
        ),
        "BG fused Pallas kernel": BGPlan(cfg=cfg, backend="fused")(noisy),
        "BG compiled plan (auto-tuned)": plan(noisy),
    }
    print(f"{'variant':34s} {'MSSIM':>8s} {'PSNR':>8s}")
    for name, img in results.items():
        print(f"{name:34s} {float(mssim(clean, img)):8.4f} "
              f"{float(psnr(clean, img)):8.2f}")

    # the paper's headline property: per-pixel cost independent of r
    print("\nwindow-radius sweep (cost should stay flat):")
    for r in (4, 8, 12, 16):
        c = BGConfig(r=r, sigma_s=8.0, sigma_r=70.0)
        bilateral_grid_filter(noisy, c).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(5):
            out = bilateral_grid_filter(noisy, c)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / 5
        print(f"  r={r:2d}: {dt*1e9/(h*w):7.2f} ns/pixel   "
              f"MSSIM {float(mssim(clean, out)):.4f}")


if __name__ == "__main__":
    main()
