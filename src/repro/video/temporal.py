"""Temporal bilateral grid: a recursive EMA of the blurred grid per stream.

Video is where the paper's real-time pipeline is actually deployed, and the
failure mode a per-frame denoiser adds there is temporal flicker: each frame's
grid is built from that frame's noise realization, so flat regions shimmer at
the grid-cell scale even when the scene is static. The fix costs no extra
kernel work on the image: carry the *blurred homogeneous grid* (the (count,
sum) pair after GF, a few hundred KiB per stream) across frames and blend it
recursively before slicing:

    B_t = blur(create(f_t))                 # per-frame GC + GF, as today
    G_t = (1 - a) * B_t + a * G_{t-1}       # temporal EMA, on the tiny grid
    out = slice(normalize(G_t), f_t)        # TI against the blended grid

Blending the homogeneous pair (not the normalized scalar grid) keeps the
semantics of eq. (4): the EMA accumulates counts and intensity sums, so the
normalized cell value is a proper weighted average over the exponential
window — empty-in-this-frame cells inherit history instead of dividing by
zero. The EMA runs on the grid, which is ``O(gx*gy*gz)`` — two to three
orders of magnitude smaller than the frame — so the temporal extension adds
no per-pixel work beyond the per-frame pipeline ("zero extra kernel cost").

Dispatch: every alpha rides the fused kernel. Since the EMA moved *into*
the fused macro-pipeline (``bg_fused_kernel_call(carry=, alpha=)`` blends
each blurred plane in VMEM right before TI slices it — see the
``repro.kernels.bg_fused`` docstring), the warm path no longer falls back to
the staged jnp pipeline: one kernel dispatch per pack, grid never leaving
on-chip memory, per-stream alpha vector mixing warm (``a > 0``), cold and
first-frame (``a == 0``) streams freely. An ``a == 0`` frame's in-kernel
blend is the exact float identity, so its output stays *bit-identical* to
the per-frame fused service no matter which streams share the pack — the
property that previously forced :class:`repro.video.session.MultiStreamPacker`
to split mixed packs into two dispatches. A pure cold pack (no carry at all)
still short-circuits to the plan's per-frame form and materializes nothing
temporal. On a mesh plan the pack's stream axis shards over the
``("batch",)`` mesh (carries travel with their stream's device, zero
collectives).

The staged jnp pipeline (vmapped ``grid_create -> grid_blur``, blend, slice)
is the temporal form of ``BGPlan(backend="reference")`` — the *reference
oracle* the fused path is tested against (the two agree to ~5e-3
pre-quantization; the fused path is authoritative in service).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bilateral_grid import (
    BGConfig,
    _round_half_up,
    conv3_axis,
    gaussian_taps,
    grid_shape,
)

__all__ = ["blurred_grid_batch", "carry_shape", "temporal_denoise"]


def carry_shape(h: int, w: int, cfg: BGConfig) -> Tuple[int, int, int, int]:
    """Shape of one stream's temporal carry: the blurred homogeneous grid
    ``(gx, gy, gz, 2)`` (channel 0 = blurred count, 1 = blurred sum)."""
    gx, gy, gz = grid_shape(h, w, cfg)
    return (gx, gy, gz, 2)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def blurred_grid_batch(
    frames: jnp.ndarray, cfg: BGConfig, precision: str = "fp32"
) -> jnp.ndarray:
    """(n, h, w) frames -> (n, gx, gy, gz, 2) blurred homogeneous grids.

    One ``B_t = blur(create(f_t))`` per frame — the quantity the temporal EMA
    is defined over. The GC cell indices for the spatial axes and the GF taps
    are frame-independent, so they are built once and shared by the whole
    batch (a ``vmap`` over ``grid_create``/``grid_blur`` would replicate
    them per frame — the same constant-hoisting the fused kernel applies to
    its column one-hots); only the intensity binning and the scatter itself
    are per-frame. Matches the per-frame ``grid_blur(grid_create(f))``
    exactly (same scatter order, same separable conv order x->y->z).

    ``precision="bf16"`` is the staged oracle's precision axis: frames are
    rounded to the bf16 storage grid before binning/scatter (as the fused
    kernel stores them), the scatter and blur accumulate fp32, and the
    returned grid is downcast to bf16 storage. ``"fp32"`` is byte-for-byte
    the pre-precision jaxpr.
    """
    if precision not in ("fp32", "bf16"):
        raise ValueError(
            f"precision must be 'fp32' or 'bf16', got {precision!r}"
        )
    frames = frames.astype(jnp.float32)
    if precision == "bf16":
        frames = frames.astype(jnp.bfloat16).astype(jnp.float32)
    b, h, w = frames.shape
    gx, gy, gz = grid_shape(h, w, cfg)
    # shared spatial cell indices (constants across the batch)
    xg = _round_half_up(jnp.arange(h, dtype=jnp.float32) / cfg.r).astype(jnp.int32)
    yg = _round_half_up(jnp.arange(w, dtype=jnp.float32) / cfg.r).astype(jnp.int32)
    zg = _round_half_up(frames / cfg.range_scale).astype(jnp.int32)  # (b, h, w)
    bi = jax.lax.broadcasted_iota(jnp.int32, (b, h, w), 0)
    vals = jnp.stack([jnp.ones((b, h, w), jnp.float32), frames], axis=-1)
    grid = jnp.zeros((b, gx, gy, gz, 2), jnp.float32)
    grid = grid.at[bi, xg[None, :, None], yg[None, None, :], zg].add(vals)
    taps = gaussian_taps(cfg)  # built once, not once per frame
    for axis in (1, 2, 3):  # batched layout (b, gx, gy, gz, 2)
        grid = conv3_axis(grid, taps, axis)
    return grid.astype(jnp.bfloat16) if precision == "bf16" else grid


def temporal_denoise(
    frames: jnp.ndarray,
    *,
    plan,
    carry: Optional[jnp.ndarray] = None,
    alpha=0.0,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """One temporal step for a pack of streams: denoise + advance the carry.

    Args:
      frames: ``(n, h, w)`` — one frame from each of n streams (or a single
        ``(h, w)`` frame, treated as n == 1).
      plan: the ``repro.plan.BGPlan`` that fixes the dispatch (backend,
        mesh, batch_tile, quantization, interpret). The temporal /
        per-frame variant of the plan is derived here from the pack
        (``as_temporal``), so one base plan serves warm, cold and mixed
        packs. ``backend="reference"`` is the staged jnp oracle.
      carry: ``None`` when no stream has temporal history, else the stacked
        ``(n, gx, gy, gz, 2)`` blurred-grid carries. Streams without history
        inside a warm pack pass a zero carry row *and* a zero alpha entry
        (the blend then reduces to ``B_t``; the packer arranges this).
      alpha: scalar or length-n host-side blend weights in ``[0, 1)``.
        ``alpha`` is configuration, not data — it must not be a traced value.

    Returns ``(out, new_carry)``. When ``carry is None``, every alpha is zero
    and the plan is a kernel plan (a pure per-frame pack) the plan's
    per-frame form is dispatched with no carry at all: the output is
    bit-identical to ``plan(frames)`` and ``new_carry`` is ``None`` —
    nothing temporal was computed, which is exactly the "reduces to the
    per-frame path at a == 0" contract. Otherwise the temporal form runs
    the EMA (in VMEM on the fused kernel; ``a == 0`` rows still
    bit-identical to the per-frame path) and the stream axis shards over
    the plan's mesh.
    """
    frames = jnp.asarray(frames)
    squeeze = frames.ndim == 2
    if squeeze:
        frames = frames[None]
    if frames.ndim != 3:
        raise ValueError(f"expected (h, w) or (n, h, w) frames, got {frames.shape}")
    n = frames.shape[0]
    alpha_np = np.broadcast_to(np.asarray(alpha, np.float32), (n,))
    if np.any(alpha_np < 0.0) or np.any(alpha_np >= 1.0):
        raise ValueError(f"temporal alpha must be in [0, 1), got {alpha}")

    if carry is None and not alpha_np.any() and plan.backend != "reference":
        out = plan.as_temporal(False)(frames)
        return (out[0] if squeeze else out), None

    if carry is None:
        # warm-up pack of a temporal stream set: no history yet, so every
        # effective alpha is 0 this step, but the carry must be produced.
        carry = jnp.zeros(
            (n,) + carry_shape(*frames.shape[1:], plan.cfg),
            plan.storage_dtype,
        )
        alpha_np = np.zeros((n,), np.float32)
    if carry.shape[0] != n:
        raise ValueError(f"carry leading axis {carry.shape[0]} != n frames {n}")
    out, new_carry = plan.as_temporal(True)(
        frames, carry=carry, alpha=jnp.asarray(alpha_np)
    )
    return (out[0] if squeeze else out), new_carry
