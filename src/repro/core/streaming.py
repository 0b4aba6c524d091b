"""Streaming stripe pipeline — the paper's macro-pipeline (Fig. 4) in JAX.

The FPGA never holds the image or the full grid: it runs GC(x) || GF(x-1) ||
TI(x-2) over row-stripes of height r with a working set of three raw grid
planes, two blurred planes, and an r-line buffer. This module reproduces that
dataflow as a ``lax.scan`` whose carry is exactly that working set, so peak
memory is O(gy*gz + r*w) instead of O(h*w + gx*gy*gz).

It is the ``"streaming"`` backend of :class:`repro.plan.BGPlan`: a plan
with that backend runs :func:`_streaming_single` per frame (vmapped over a
batch, and sharded over the plan's batch mesh if it has one). Equivalence
with the whole-image path is exact (same arithmetic order per plane) and
asserted in tests.

Key regularity (the paper's counter logic): for a stripe starting at row s*r,
round((s*r + i)/r) - s = round(i/r) and floor((s*r + i)/r) - s = 0 for
0 <= i < r — so the per-stripe scatter pattern and interpolation fractions are
*static*, independent of the stripe index. That is what lets the FPGA use
counters instead of address arithmetic, and what lets us scan a single traced
stripe body here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .bilateral_grid import (
    BGConfig,
    conv3_axis,
    _round_half_up,
    _trilerp_weights,
    gaussian_taps,
    grid_shape,
    quantize_intensity,
)


def _streaming_single(
    image: jnp.ndarray, cfg: BGConfig, quantize_output: bool
) -> jnp.ndarray:
    image = image.astype(jnp.float32)
    h, w = image.shape
    r = cfg.r
    _, gy, gz = grid_shape(h, w, cfg)
    n_stripes = -(-h // r)  # ceil
    hp = n_stripes * r
    taps = gaussian_taps(cfg)

    # pad rows to a whole number of stripes; padded rows are masked out of GC
    img_p = jnp.pad(image, ((0, hp - h), (0, 0)))
    valid = jnp.pad(jnp.ones((h, w), jnp.float32), ((0, hp - h), (0, 0)))
    stripes = img_p.reshape(n_stripes, r, w)
    stripe_mask = valid.reshape(n_stripes, r, w)

    # --- static per-stripe index patterns (the paper's counters/LUT L2) ---
    i_local = np.arange(r)
    xg_local = ((2 * i_local + r) // (2 * r)).astype(np.int32)  # round(i/r): 0|1
    xf_local = jnp.asarray(i_local / r, jnp.float32)  # frac of floor lerp
    iy = np.arange(w)
    yg = jnp.asarray((2 * iy + r) // (2 * r), np.int32)  # GC round(iy/r)
    y0 = jnp.asarray(iy // r, np.int32)  # TI floor
    yf = jnp.asarray(iy / r - iy // r, jnp.float32)
    xg_local = jnp.asarray(xg_local)

    inv_rs = 1.0 / cfg.range_scale

    def gc_stripe(px: jnp.ndarray, msk: jnp.ndarray) -> jnp.ndarray:
        """Scatter an (r, w) stripe into contributions for planes (s, s+1).

        Returns (2, gy, gz, 2): leading axis = x-plane offset from the stripe
        index; trailing = (count, sum)."""
        zg = _round_half_up(px * inv_rs).astype(jnp.int32)
        x_idx = jnp.broadcast_to(xg_local[:, None], (r, w))
        y_idx = jnp.broadcast_to(yg[None, :], (r, w))
        vals = jnp.stack([msk, px * msk], axis=-1)
        out = jnp.zeros((2, gy, gz, 2), jnp.float32)
        return out.at[x_idx, y_idx, zg].add(vals)

    def blur_plane(r2, r1, r0):
        """3x3x3 blur of the middle raw plane given (prev, mid, next) planes."""
        mix = taps[0] * r2 + taps[1] * r1 + taps[2] * r0  # x-axis conv
        mix = conv3_axis(mix, taps, 0)  # y axis
        mix = conv3_axis(mix, taps, 1)  # z axis
        return mix  # (gy, gz, 2) homogeneous

    def normalize(b):
        return jnp.where(b[..., 0] > 1e-12, b[..., 1] / jnp.maximum(b[..., 0], 1e-12), 0.0)

    def ti_stripe(px, b_lo, b_hi):
        """TI for an (r, w) stripe given blurred planes floor(x) and floor(x)+1.

        In 'paper' mode b_* are normalized scalars (gy, gz); in 'classic' mode
        they are homogeneous (gy, gz, 2) and division happens per pixel."""
        fz = px * inv_rs
        z0 = jnp.floor(fz).astype(jnp.int32)
        zf = fz - z0
        wz0, wz1 = _trilerp_weights(zf)
        wx0, wx1 = _trilerp_weights(xf_local[:, None])  # (r, 1)
        wy0, wy1 = _trilerp_weights(yf[None, :])  # (1, w)
        y0b = jnp.broadcast_to(y0[None, :], (r, w))

        def interp(plane):
            acc = jnp.zeros(px.shape[:2] + plane.shape[2:], jnp.float32)
            for dj, wyj in ((0, wy0), (1, wy1)):
                for dk, wzk in ((0, wz0), (1, wz1)):
                    c = plane[y0b + dj, z0 + dk]
                    wgt = (wyj * wzk)
                    acc = acc + (wgt[..., None] if c.ndim == 3 else wgt) * c
            return acc

        lo = interp(b_lo)
        hi = interp(b_hi)
        if lo.ndim == 3:  # classic: homogeneous lerp then divide
            v = (wx0[..., None] if lo.ndim == 3 else wx0) * lo
            v = v + (wx1[..., None] if hi.ndim == 3 else wx1) * hi
            return jnp.where(v[..., 0] > 1e-12, v[..., 1] / jnp.maximum(v[..., 0], 1e-12), 0.0)
        return wx0 * lo + wx1 * hi

    plane_h = (gy, gz, 2)
    scalar_plane = (gy, gz) if cfg.normalize_mode == "paper" else (gy, gz, 2)

    def step(carry, xs):
        R2, R1, Apart, B1, S2, S1 = carry
        px, msk = xs
        contrib = gc_stripe(px, msk)
        R0 = Apart + contrib[0]  # raw plane s complete
        Apart_next = contrib[1]
        blurred = blur_plane(R2, R1, R0)  # blurred plane s-1
        Bnew = normalize(blurred) if cfg.normalize_mode == "paper" else blurred
        out = ti_stripe(S2, B1, Bnew)  # TI of stripe s-2 (planes s-2, s-1)
        return (R1, R0, Apart_next, Bnew, S1, px), out

    zero_plane = jnp.zeros(plane_h, jnp.float32)
    zero_b = jnp.zeros(scalar_plane, jnp.float32)
    zero_stripe = jnp.zeros((r, w), jnp.float32)
    carry0 = (zero_plane, zero_plane, zero_plane, zero_b, zero_stripe, zero_stripe)

    # feed n_stripes real stripes + 2 epilogue zero stripes
    xs_px = jnp.concatenate([stripes, jnp.zeros((2, r, w), jnp.float32)], 0)
    xs_mk = jnp.concatenate([stripe_mask, jnp.zeros((2, r, w), jnp.float32)], 0)
    _, outs = jax.lax.scan(step, carry0, (xs_px, xs_mk))

    out = outs[2:].reshape(hp, w)[:h]
    if quantize_output:
        out = quantize_intensity(out, cfg)
    return out
