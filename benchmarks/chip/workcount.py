"""The work one frame needs, counted from the configuration's shapes alone,
and the peaks of the chips the benchmark runs on.

The count is the algorithm's, not an implementation's: it never looks at a
plan's backend, tile, padding or storage dtype, so every implementation of
the same configuration is held to the same least time.

Bytes a deployment must move per frame: the 8-bit frame in and the 8-bit
frame out (1 byte per pixel each way), and in temporal cells one read and
one write of the ``(gx, gy, gz, 2)`` grid carry at the configuration's
stated precision.

Operations per frame (the paper's arithmetic, Algorithm 1):
  GC  per pixel: the z coordinate f / (r sr / ss) and its rounding (2), and
      the two accumulations of (1, f) into the cell (2)               = 4
  GF  per cell: three separable 3-tap passes over both channels,
      3 multiplies + 2 adds each (3 * 2 * 5 = 30), and the division of
      eq. (4) (1)                                                     = 31
  EMA per cell, temporal only: (1 - a) B + a G on both channels       = 6
  TI  per pixel: z coordinate, its fraction and 1 - fraction (3), eight
      corner weights of two multiplies each (16), eight multiply-adds
      (16), and the output rounding (1)                               = 36
The row and column coordinates of TI are shared by a whole row or column
and are not counted.

Least time = max(bytes / peak bytes/s, operations / peak operations/s);
the larger term names the bound.
"""
from __future__ import annotations

import math

# One TPU v5e chip (Google Cloud documentation, "TPU v5e": 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). JAX names it "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "flops_per_s": 197e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

GC_OPS_PER_PIXEL = 4
TI_OPS_PER_PIXEL = 36
GF_OPS_PER_CELL = 31
EMA_OPS_PER_CELL = 6
PRECISION_BYTES = {"fp32": 4, "bf16": 2}


def grid_cells(cfg: dict) -> int:
    r = cfg["r"]
    gz = int(math.floor(cfg["intensity_max"] / (r * cfg["sigma_r"] / cfg["sigma_s"]))) + 2
    return (cfg["height"] // r + 2) * (cfg["width"] // r + 2) * gz


def frame_work(cfg: dict) -> dict:
    """``{"bytes": ..., "flops": ...}`` for one frame of configuration
    ``cfg`` (a configuration file's contents)."""
    pixels = cfg["height"] * cfg["width"]
    pixel_bytes = math.ceil(cfg["bits"] / 8)
    cells = grid_cells(cfg)
    nbytes = 2 * pixels * pixel_bytes
    flops = (GC_OPS_PER_PIXEL + TI_OPS_PER_PIXEL) * pixels + GF_OPS_PER_CELL * cells
    if cfg.get("temporal"):
        nbytes += 2 * cells * 2 * PRECISION_BYTES[cfg["precision"]]
        flops += EMA_OPS_PER_CELL * cells
    return {"bytes": nbytes, "flops": flops}


def least_time_s(cfg: dict, device_kind: str):
    """``(seconds, bound)`` for one frame on one chip of ``device_kind``;
    ``bound`` is ``"bytes"`` or ``"flops"``. An unknown chip is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them to PEAKS")
    peak = PEAKS[device_kind]
    work = frame_work(cfg)
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_flops = work["flops"] / peak["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
