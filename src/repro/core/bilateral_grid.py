"""Bilateral grid with a variable-sized window (Hashimoto & Takamaeda-Yamazaki, 2021).

The classic bilateral grid (Chen/Paris/Durand 2007) fixes the blur footprint on
the *grid*; this paper re-derives the grid so that the bilateral-filter window
radius ``r`` lives on the *input image*:

    fv(i) = (ix / r,  iy / r,  f(i) / (r * sigma_r / sigma_s))

and the grid-space blur is always a 3x3x3 Gaussian with ``sigma_g = sigma_s/r``.
The pipeline is three stages, exactly as the paper's Algorithm 1:

  GC  (grid creation)          grid[round(fv(i))] += (1, f(i))
  GF  (3^3 Gaussian filter)    grid_f = blur(grid);  normalized per cell (eq. 4)
  TI  (trilinear interpolation) out(i) = trilerp(grid_f, fv(i))        (eq. 5)

Two normalization orders are supported:
  * ``"paper"``   — eq. (4)/Algorithm 1: divide blurred sum by blurred count per
                    grid cell (0 where empty), then interpolate the scalar grid.
                    This is what the FPGA implements.
  * ``"classic"`` — eq. (2)/Chen et al.: interpolate the homogeneous
                    (sum, count) pair and divide at the slice point.

All arrays are float32 image intensities in [0, intensity_max]; shape (h, w).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BGConfig",
    "conv3_axis",
    "gaussian_taps",
    "grid_shape",
    "grid_create",
    "grid_blur",
    "grid_normalize",
    "grid_slice",
    "grid_slice_homogeneous",
    "bilateral_grid_filter",
    "quantize_intensity",
]


def _round_half_up(v: jnp.ndarray) -> jnp.ndarray:
    """Deterministic round-half-up, used for every [.] in the paper."""
    return jnp.floor(v + 0.5)


def quantize_intensity(out: jnp.ndarray, cfg: "BGConfig") -> jnp.ndarray:
    """The paper's output quantization: round-half-up, clip to the intensity
    range. The single source of truth for every pipeline exit (jnp reference,
    streaming scan, Pallas kernels, sharded service path)."""
    return jnp.clip(_round_half_up(out), 0.0, cfg.intensity_max)


@dataclasses.dataclass(frozen=True)
class BGConfig:
    """Static configuration of the variable-window bilateral grid.

    Attributes:
      r:         window radius on the *input image* (the paper's key parameter).
      sigma_s:   spatial Gaussian std-dev, in input-image pixels.
      sigma_r:   range Gaussian std-dev, in intensity units.
      intensity_max: top of the intensity range (255 for 8-bit).
      normalize_mode: "paper" (eq. 4, per-cell after GF) or "classic" (eq. 2).
      weight_mode: "float" exact Gaussian taps, or "pow2" taps quantized to
          powers of two (the paper's shift-only arithmetic, Figs. 7-8).
    """

    r: int
    sigma_s: float
    sigma_r: float
    intensity_max: float = 255.0
    normalize_mode: str = "paper"
    weight_mode: str = "float"

    def __post_init__(self):
        if self.r < 1:
            raise ValueError(f"window radius must be >= 1, got {self.r}")
        if self.sigma_s <= 0 or self.sigma_r <= 0:
            raise ValueError("sigma_s and sigma_r must be positive")
        if self.normalize_mode not in ("paper", "classic"):
            raise ValueError(f"bad normalize_mode {self.normalize_mode!r}")
        if self.weight_mode not in ("float", "pow2"):
            raise ValueError(f"bad weight_mode {self.weight_mode!r}")

    # ---- derived quantities (all static Python numbers) ----
    @property
    def range_scale(self) -> float:
        """Divisor of the intensity axis: r * sigma_r / sigma_s."""
        return self.r * self.sigma_r / self.sigma_s

    @property
    def sigma_g(self) -> float:
        """Grid-space Gaussian std-dev (isotropic after rescaling)."""
        return self.sigma_s / self.r

    @property
    def gz(self) -> int:
        return int(np.floor(self.intensity_max / self.range_scale)) + 2


def grid_shape(h: int, w: int, cfg: BGConfig) -> Tuple[int, int, int]:
    """(gx, gy, gz) per the paper: (floor(h/r)+2, floor(w/r)+2, floor(I/rs)+2).

    Note the paper indexes x by image *rows* (height) and y by columns.
    """
    gx = h // cfg.r + 2
    gy = w // cfg.r + 2
    return (gx, gy, cfg.gz)


def gaussian_taps(cfg: BGConfig) -> jnp.ndarray:
    """1-D taps [e, 1, e] with e = exp(-1/(2 sigma_g^2)).

    The 27 3-D weights are the separable outer product of these taps; in
    ``pow2`` mode each tap is quantized to the nearest power of two so every
    multiply is a shift (products of pow2 taps stay pow2 — faithful to the
    paper's shift-only GF/TI arithmetic).
    """
    e = float(np.exp(-1.0 / (2.0 * cfg.sigma_g**2)))
    if cfg.weight_mode == "pow2":
        # Quantize to 2^round(log2(e)); e==0 underflow maps to the smallest
        # representable shift (2^-30) i.e. effectively zero.
        if e <= 2.0**-30:
            e = 0.0
        else:
            e = float(2.0 ** np.round(np.log2(e)))
    return jnp.asarray([e, 1.0, e], dtype=jnp.float32)


# --------------------------------------------------------------------------
# GC — grid creation
# --------------------------------------------------------------------------

def feature_coords(h: int, w: int, image: jnp.ndarray, cfg: BGConfig):
    """fv(i) components: (ix/r, iy/r, f(i)/range_scale). Shapes (h,), (w,), (h,w)."""
    fx = jnp.arange(h, dtype=jnp.float32) / cfg.r
    fy = jnp.arange(w, dtype=jnp.float32) / cfg.r
    fz = image.astype(jnp.float32) / cfg.range_scale
    return fx, fy, fz


@partial(jax.jit, static_argnames=("cfg",))
def grid_create(image: jnp.ndarray, cfg: BGConfig) -> jnp.ndarray:
    """GC: scatter each pixel's (1, f) into grid[round(fv)].

    Returns float32 grid of shape (gx, gy, gz, 2) with channel 0 = pixel count
    and channel 1 = intensity sum (the paper's bit-packed homogeneous pair).
    """
    h, w = image.shape
    gx, gy, gz = grid_shape(h, w, cfg)
    fx, fy, fz = feature_coords(h, w, image, cfg)
    xg = _round_half_up(fx).astype(jnp.int32)  # (h,)
    yg = _round_half_up(fy).astype(jnp.int32)  # (w,)
    zg = _round_half_up(fz).astype(jnp.int32)  # (h,w)

    x_idx = jnp.broadcast_to(xg[:, None], (h, w))
    y_idx = jnp.broadcast_to(yg[None, :], (h, w))
    vals = jnp.stack(
        [jnp.ones((h, w), jnp.float32), image.astype(jnp.float32)], axis=-1
    )
    grid = jnp.zeros((gx, gy, gz, 2), jnp.float32)
    return grid.at[x_idx, y_idx, zg].add(vals)


# --------------------------------------------------------------------------
# GF — 3x3x3 Gaussian filter on the grid
# --------------------------------------------------------------------------

def conv3_axis(x: jnp.ndarray, taps, axis: int) -> jnp.ndarray:
    """Width-3 conv along ``axis`` with zero boundary (paper's implicit border).

    This is the single shared GF building block (also re-exported through
    ``repro.kernels.common``). It is layout-agnostic: ``axis`` is a position in
    whatever layout the caller uses — (gx, gy, gz, 2) here, (..., gz, gy) in
    the TPU kernels, (gy, gz, 2) in the streaming scan — so the caller's
    comment, not this helper, names which grid axis is being blurred.
    """
    # shift by one with a zero slab in place of the wrapped slice: exact,
    # no scatter (the Pallas TPU lowering has none), and the same bits as
    # rolling and then zeroing the wrapped slice
    n = x.shape[axis]
    zero = jnp.zeros_like(jax.lax.slice_in_dim(x, 0, 1, axis=axis))
    lo = jnp.concatenate(
        [zero, jax.lax.slice_in_dim(x, 0, n - 1, axis=axis)], axis=axis
    )
    hi = jnp.concatenate(
        [jax.lax.slice_in_dim(x, 1, n, axis=axis), zero], axis=axis
    )
    return taps[0] * lo + taps[1] * x + taps[2] * hi


@partial(jax.jit, static_argnames=("cfg",))
def grid_blur(grid: jnp.ndarray, cfg: BGConfig) -> jnp.ndarray:
    """GF numerator+denominator together: separable 3-tap blur on both channels.

    The paper computes the numerator and denominator of eq. (4) in one pass
    thanks to the packed (count, sum) layout; the separable form is exact
    because the 27 weights are the outer product g(wx) g(wy) g(wz).
    """
    taps = gaussian_taps(cfg)
    out = grid
    for axis in range(3):  # grid layout (gx, gy, gz, 2): axes 0/1/2 = x/y/z
        out = conv3_axis(out, taps, axis)
    return out


def grid_normalize(blurred: jnp.ndarray) -> jnp.ndarray:
    """Eq. (4): grid_f = blurred_sum / blurred_count, 0 where count == 0."""
    count = blurred[..., 0]
    summ = blurred[..., 1]
    return jnp.where(count > 1e-12, summ / jnp.maximum(count, 1e-12), 0.0)


# --------------------------------------------------------------------------
# TI — trilinear interpolation (slice)
# --------------------------------------------------------------------------

def _trilerp_weights(frac: jnp.ndarray):
    """(w0, w1) = (1-frac, frac): standard trilinear corner weights.

    Eq. (5) as printed assigns corner (i,j,k) weight |p - floor(p) - (i,j,k)|,
    which is the weight of the *opposite* corner; we implement the standard
    form (see DESIGN.md §8.4).
    """
    return 1.0 - frac, frac


@partial(jax.jit, static_argnames=("cfg",))
def grid_slice(grid_f: jnp.ndarray, image: jnp.ndarray, cfg: BGConfig) -> jnp.ndarray:
    """TI of a scalar grid at fv(i) for every pixel i. Returns float (h, w).

    ``image`` is the original input (its intensities give the z coordinate).
    """
    h, w = image.shape
    fx, fy, fz = feature_coords(h, w, image, cfg)
    x0 = jnp.floor(fx).astype(jnp.int32)  # (h,)
    y0 = jnp.floor(fy).astype(jnp.int32)  # (w,)
    z0 = jnp.floor(fz).astype(jnp.int32)  # (h,w)
    xf = (fx - x0)[:, None]  # (h,1)
    yf = (fy - y0)[None, :]  # (1,w)
    zf = fz - z0             # (h,w)

    x0b = jnp.broadcast_to(x0[:, None], (h, w))
    y0b = jnp.broadcast_to(y0[None, :], (h, w))

    wx0, wx1 = _trilerp_weights(xf)
    wy0, wy1 = _trilerp_weights(yf)
    wz0, wz1 = _trilerp_weights(zf)

    out = jnp.zeros((h, w), jnp.float32)
    for di, wxi in ((0, wx0), (1, wx1)):
        for dj, wyj in ((0, wy0), (1, wy1)):
            for dk, wzk in ((0, wz0), (1, wz1)):
                corner = grid_f[x0b + di, y0b + dj, z0 + dk]
                out = out + wxi * wyj * wzk * corner
    return out


@partial(jax.jit, static_argnames=("cfg",))
def grid_slice_homogeneous(
    blurred: jnp.ndarray, image: jnp.ndarray, cfg: BGConfig
) -> jnp.ndarray:
    """Classic-BG slice (eq. 2): interpolate (sum, count), divide at the point."""
    num = grid_slice(blurred[..., 1], image, cfg)
    den = grid_slice(blurred[..., 0], image, cfg)
    return jnp.where(den > 1e-12, num / jnp.maximum(den, 1e-12), 0.0)


# --------------------------------------------------------------------------
# Full pipeline
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "quantize_output"))
def bilateral_grid_filter(
    image: jnp.ndarray, cfg: BGConfig, quantize_output: bool = True
) -> jnp.ndarray:
    """GC -> GF -> TI. Input float32 (h, w) in [0, intensity_max].

    ``quantize_output=True`` rounds to integers and clips to the intensity
    range (the paper's output is 8-bit); False returns the raw float surface
    (useful for gradient-based use and tighter numerical comparisons).
    """
    image = image.astype(jnp.float32)
    grid = grid_create(image, cfg)
    blurred = grid_blur(grid, cfg)
    if cfg.normalize_mode == "paper":
        grid_f = grid_normalize(blurred)
        out = grid_slice(grid_f, image, cfg)
    else:
        out = grid_slice_homogeneous(blurred, image, cfg)
    if quantize_output:
        out = quantize_intensity(out, cfg)
    return out
