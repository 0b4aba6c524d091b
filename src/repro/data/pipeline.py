"""Data pipeline with the paper's BG denoiser as a first-class stage.

This is where the paper's contribution plugs into the LM framework
(DESIGN.md §Arch-applicability): the [vlm] image frontend and the [audio]
spectrogram frontend both run bilateral-grid denoising before patch/frame
embedding. Each stage takes a compiled :class:`repro.plan.BGPlan` and calls
it, so the plan alone picks the backend (vmapped jnp reference, fused Pallas
kernel, batch-axis device-sharded kernel, streamed input DMA) and the
frontends ride the same hot path the serving engine does.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bilateral_grid import BGConfig
from repro.plan import BGPlan

__all__ = ["patchify_embed", "vlm_preprocess", "spectrogram_denoise"]

# The [audio] stage's documented default: a small window over the
# spectrogram's time/frequency axes, run by the jnp reference.
SPECTROGRAM_PLAN = BGPlan(
    cfg=BGConfig(r=4, sigma_s=2.0, sigma_r=40.0), backend="reference"
)


def patchify_embed(
    images: jnp.ndarray, patch: int, dim: int, seed: int = 0
) -> jnp.ndarray:
    """(B,H,W) -> (B, n_patches, dim) with a fixed random projection.

    Stands in for the learned patch-embedding of the stubbed vision tower;
    deterministic so tests can assert exact shapes/values.
    """
    B, H, W = images.shape
    hp, wp = H // patch, W // patch
    x = images[:, : hp * patch, : wp * patch]
    x = x.reshape(B, hp, patch, wp, patch).transpose(0, 1, 3, 2, 4)
    x = x.reshape(B, hp * wp, patch * patch) / 255.0
    key = jax.random.PRNGKey(seed)
    proj = jax.random.normal(key, (patch * patch, dim), jnp.float32) * (
        1.0 / np.sqrt(patch * patch)
    )
    return x @ proj


def vlm_preprocess(
    images: jnp.ndarray, plan: Optional[BGPlan], patch: int, dim: int
) -> jnp.ndarray:
    """Full [vlm] frontend stage: BG denoise -> patchify -> project.

    ``plan`` denoises the ``(B, H, W)`` batch; ``None`` embeds the images
    as they are.
    """
    if plan is not None:
        images = plan(images)
    return patchify_embed(images, patch, dim)


def spectrogram_denoise(spec: jnp.ndarray, plan: BGPlan = SPECTROGRAM_PLAN):
    """[audio] stage: treat a (B, T, F) spectrogram as images in [0,255],
    denoise them with ``plan`` and map the answer back to the input range."""
    lo = jnp.min(spec)
    hi = jnp.max(spec)
    scaled = (spec - lo) / jnp.maximum(hi - lo, 1e-9) * 255.0
    den = plan(scaled)
    return den / 255.0 * (hi - lo) + lo
