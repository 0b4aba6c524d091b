"""BG kernel hillclimb measurements (EXPERIMENTS.md §Perf, cell 3).

Staged (GC->HBM->GF->HBM->TI) vs fused macro-pipeline kernel:
  * analytic per-frame HBM traffic (exact buffer sizes — what the FPGA's
    "low memory footprint" claim becomes on a TPU),
  * v5e roofline terms for both variants,
  * interpret-mode wall time at a reduced size (functional check; interpret
    timing is not a TPU proxy and is labeled as such).
"""
import time

import jax
import jax.numpy as jnp

from repro.core import BGConfig, add_gaussian_noise, grid_shape, synthetic_image
from repro.launch.hlo_analysis import TARGET_KIND, chip_peaks
from repro.plan import BGPlan

HBM_BW = chip_peaks(TARGET_KIND)["hbm_bw"]
PEAK = chip_peaks(TARGET_KIND)["peak_flops"]


def traffic_model(h, w, cfg):
    """Per-frame HBM bytes for the staged vs fused kernel pipelines (fp32)."""
    gx, gy, gz = grid_shape(h, w, cfg)
    img = h * w * 4
    grid = gx * gy * gz * 2 * 4
    gridf = gx * gy * gz * 4
    staged = (
        (img + grid)          # GC: read image, write grid
        + (grid + grid)       # GF: read grid, write blurred grid
        + (grid + gridf)      # normalize: read blurred, write grid_f
        + (gridf + img + img) # TI: read grid_f + image, write out
    )
    fused = img + img  # one image read, one image write; grid lives in VMEM
    # per-pixel create/slice flops ~ O(1); blur 27*2 flops per grid cell
    flops = h * w * (gz + 8 * 3 * 2) + gx * gy * gz * 2 * 27 * 2
    return staged, fused, flops


def run(quick: bool = False):
    rows = []
    # analytic model at the paper's full-HD size
    for r in (4, 8, 12, 16):
        cfg = BGConfig(r=r, sigma_s=8.0, sigma_r=70.0)
        staged, fused, flops = traffic_model(1080, 1920, cfg)
        t_staged = staged / HBM_BW
        t_fused = fused / HBM_BW
        rows.append(
            (
                f"bg_kernels/traffic_fullhd_r{r}",
                t_fused * 1e6,
                f"staged_bytes={staged/1e6:.1f}MB fused_bytes={fused/1e6:.1f}MB "
                f"ratio={staged/fused:.2f}x flops={flops/1e6:.0f}M "
                f"mem_term_fused_us={t_fused*1e6:.1f} compute_term_us={flops/PEAK*1e6:.2f}",
            )
        )
    # functional wall-time (interpret mode) at reduced size
    h, w = (64, 96) if quick else (135, 240)
    noisy = add_gaussian_noise(synthetic_image(h, w), 30.0)
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    for fused in (False, True):
        plan = BGPlan(cfg=cfg, backend="fused" if fused else "staged")
        out = plan(noisy)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = plan(noisy)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        rows.append(
            (
                f"bg_kernels/interpret_{'fused' if fused else 'staged'}_{h}x{w}",
                dt * 1e6,
                "interpret-mode functional timing (not a TPU proxy)",
            )
        )
    return rows
