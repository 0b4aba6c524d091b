"""Plain jax.numpy bilateral grid with a variable-sized window, and its
temporal grid EMA: the yardstick that decides whether a run is correct.

It follows arXiv:2110.07186 (Hashimoto & Takamaeda-Yamazaki, 2021),
Algorithm 1, on its own and imports nothing of the program under test:

  fv(i)  = (ix / r, iy / r, f(i) / (r * sigma_r / sigma_s))
  GC     grid[round(fv(i))] += (1, f(i))                  (round half up)
  GF     3x3x3 separable Gaussian, taps (e, 1, e), e = exp(-1 / (2 sg^2)),
         sg = sigma_s / r, zero outside the grid
  norm   grid_f = sum / count per cell, 0 where count is 0    (eq. 4)
  TI     out(i) = trilinear interpolation of grid_f at fv(i)  (eq. 5)
  quant  out = clip(floor(out + 0.5), 0, 255)

Temporal (one stream): G_0 = B_0, G_t = (1 - a) B_t + a G_{t-1}, where
B_t = GF(GC(f_t)) is the blurred (count, sum) grid, and frame t is sliced
from normalize(G_t).

Everything is float32 with matrix products at ``Precision.HIGHEST``. The
grid of 8-bit frames holds integer counts and sums, so GC is exact in any
summation order.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def grid_dims(h: int, w: int, r: int, sigma_s: float, sigma_r: float,
              intensity_max: float = 255.0):
    """(gx, gy, gz): floor(h/r)+2, floor(w/r)+2, floor(I/(r sr/ss))+2."""
    range_scale = r * sigma_r / sigma_s
    return h // r + 2, w // r + 2, int(math.floor(intensity_max / range_scale)) + 2


def _onehot_rows(n: int, r: int, cells: int) -> jnp.ndarray:
    """(cells, n) 0/1 matrix: pixel i of an axis lies in cell floor(i/r+1/2)."""
    idx = jnp.floor(jnp.arange(n, dtype=jnp.float32) / r + 0.5).astype(jnp.int32)
    return (jnp.arange(cells)[:, None] == idx[None, :]).astype(jnp.float32)


def _blur_axis(g: jnp.ndarray, e: float, axis: int) -> jnp.ndarray:
    n = g.shape[axis]
    pad = [(0, 0)] * g.ndim
    pad[axis] = (1, 1)
    p = jnp.pad(g, pad)
    lo = jax.lax.slice_in_dim(p, 0, n, axis=axis)
    hi = jax.lax.slice_in_dim(p, 2, n + 2, axis=axis)
    return e * lo + g + e * hi


def blurred_grid(frame: jnp.ndarray, params: dict) -> jnp.ndarray:
    """GF(GC(frame)) as a (gx, gy, gz, 2) float32 grid: (count, sum)."""
    r, ss, sr = params["r"], params["sigma_s"], params["sigma_r"]
    h, w = frame.shape
    gx, gy, gz = grid_dims(h, w, r, ss, sr)
    f = frame.astype(jnp.float32)
    zbin = jnp.floor(f / (r * sr / ss) + 0.5).astype(jnp.int32)     # (h, w)
    zhot = (zbin[..., None] == jnp.arange(gz)).astype(jnp.float32)   # (h, w, gz)
    vals = jnp.stack([zhot, zhot * f[..., None]], axis=-1)           # (h, w, gz, 2)
    rows = _onehot_rows(h, r, gx)                                    # (gx, h)
    cols = _onehot_rows(w, r, gy)                                    # (gy, w)
    grid = jnp.einsum("xh,hwzc->xwzc", rows, vals, precision=HIGHEST)
    grid = jnp.einsum("yw,xwzc->xyzc", cols, grid, precision=HIGHEST)
    e = float(np.exp(-1.0 / (2.0 * (ss / r) ** 2)))
    for axis in range(3):
        grid = _blur_axis(grid, e, axis)
    return grid


def slice_grid(grid: jnp.ndarray, frame: jnp.ndarray, params: dict) -> jnp.ndarray:
    """Normalize the (count, sum) grid and interpolate it at every pixel;
    returns the quantized 8-bit output as float32 (h, w)."""
    r, ss, sr = params["r"], params["sigma_s"], params["sigma_r"]
    h, w = frame.shape
    count, total = grid[..., 0], grid[..., 1]
    gf = jnp.where(count > 0, total / jnp.where(count > 0, count, 1.0), 0.0)
    fx = jnp.arange(h, dtype=jnp.float32) / r
    fy = jnp.arange(w, dtype=jnp.float32) / r
    fz = frame.astype(jnp.float32) / (r * sr / ss)
    x0, y0, z0 = (jnp.floor(v).astype(jnp.int32) for v in (fx, fy, fz))
    xf, yf, zf = fx - x0, fy - y0, fz - z0
    # along x: (h, gy, gz); then y: (h, w, gz); then z: (h, w)
    gxi = (1 - xf)[:, None, None] * gf[x0] + xf[:, None, None] * gf[x0 + 1]
    gyi = (1 - yf)[None, :, None] * gxi[:, y0] + yf[None, :, None] * gxi[:, y0 + 1]
    lo = jnp.take_along_axis(gyi, z0[..., None], axis=2)[..., 0]
    hi = jnp.take_along_axis(gyi, z0[..., None] + 1, axis=2)[..., 0]
    out = (1 - zf) * lo + zf * hi
    return jnp.clip(jnp.floor(out + 0.5), 0.0, 255.0)


def _key(params: dict) -> tuple:
    return params["r"], params["sigma_s"], params["sigma_r"]


@partial(jax.jit, static_argnums=(1,))
def _filter_batch(frames, key):
    params = dict(zip(("r", "sigma_s", "sigma_r"), key))
    return jax.vmap(lambda f: slice_grid(blurred_grid(f, params), f, params))(frames)


def denoise(frames: np.ndarray, params: dict, block: int = 4) -> np.ndarray:
    """Per-frame filter of (n, h, w) 8-bit frames, ``block`` frames per call;
    returns uint8 (n, h, w)."""
    out = []
    for i in range(0, len(frames), block):
        chunk = np.asarray(frames[i:i + block])
        if len(chunk) < block:  # one compiled shape for every block
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], block - len(chunk), 0)])
        got = np.asarray(_filter_batch(jnp.asarray(chunk), _key(params)))
        out.append(got[: min(block, len(frames) - i)].astype(np.uint8))
    return np.concatenate(out)


@partial(jax.jit, static_argnums=(1,))
def _grids(frames, key):
    params = dict(zip(("r", "sigma_s", "sigma_r"), key))
    return jax.vmap(lambda f: blurred_grid(f, params))(frames)


@partial(jax.jit, static_argnums=(2,))
def _slice_one(grid, frame, key):
    return slice_grid(grid, frame, dict(zip(("r", "sigma_s", "sigma_r"), key)))


def temporal_stream(pool, order, alpha: float, params: dict, keep, block: int = 8) -> dict:
    """Replay one stream from its first frame through the grid EMA.

    The stream's frame t is ``pool[order[t]]``; the whole sequence
    ``order`` is replayed in order. Returns ``{t: uint8 output}`` for each
    position t in ``keep``. Each pool frame is gridded once; every position
    is blended; only the kept ones are sliced.
    """
    key = _key(params)
    grids = []
    for i in range(0, len(pool), block):
        chunk = np.asarray(pool[i:i + block])
        m = len(chunk)
        if m < block:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], block - m, 0)])
        grids.extend(list(_grids(jnp.asarray(chunk), key)[:m]))
    keep = set(int(t) for t in keep)
    out = {}
    carry = None
    for t, k in enumerate(order):
        b = grids[k]
        carry = b if carry is None else _blend(b, carry, alpha)
        if t in keep:
            out[t] = np.asarray(_slice_one(carry, jnp.asarray(pool[k]), key)).astype(np.uint8)
    return out


@jax.jit
def _blend(b, carry, alpha):
    return (1.0 - alpha) * b + alpha * carry
