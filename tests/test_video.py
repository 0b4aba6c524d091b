"""Temporal bilateral grid + stream sessions.

The contracts under test:
  * ``alpha == 0`` is the per-frame fused service path, *bit-identically*,
    across ragged multi-stream shapes (h % r != 0, w % r != 0, n odd) — the
    temporal subsystem must cost nothing when switched off;
  * a warm-up pack (``alpha > 0``, no history) is bit-identical to the
    per-frame fused path (effective alpha 0 on the fused temporal kernel),
    while the staged oracle (``BGPlan(backend="reference")``) still equals
    the jnp reference exactly;
  * on a static scene, PSNR improves monotonically with alpha (the EMA
    accumulates evidence instead of flickering);
  * per-stream carries never leak across streams in the multi-stream packer;
  * the ``synthetic_video`` fixture is deterministic and actually pans.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise, bilateral_grid_filter, psnr
from repro.core.bilateral_grid import quantize_intensity
from repro.data import synthetic_video
from repro.kernels import bg_fused
from repro.plan import BGPlan
from repro.sharding.bg_shard import batch_mesh
from repro.video import (
    MultiStreamPacker,
    blurred_grid_batch,
    carry_shape,
    temporal_denoise,
)

CFG = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
# the fused service plan, on a batch mesh over all local devices
PLAN = BGPlan(cfg=CFG, mesh=batch_mesh(), interpret=True)
STAGED = BGPlan(cfg=CFG, backend="reference")  # the staged jnp oracle

# ragged (h, w) wrt r=6, stream counts covering n == 1 and odd packs
RAGGED_PACKS = [((45, 55), 1), ((45, 55), 3), ((33, 47), 5)]


def _noisy_stack(n, h, w, seed=0):
    vid = synthetic_video(seed, n, h, w, motion=1.5)
    return jnp.stack(
        [add_gaussian_noise(vid[t], 30.0, seed=seed + 10 * t) for t in range(n)]
    )


@pytest.mark.parametrize("shape,n", RAGGED_PACKS)
def test_alpha0_bit_identical_to_fused_per_frame(shape, n):
    h, w = shape
    assert h % CFG.r and w % CFG.r  # genuinely ragged
    frames = _noisy_stack(n, h, w)
    out, carry = temporal_denoise(frames, plan=PLAN, alpha=0.0)
    assert carry is None  # nothing temporal was computed
    ref = quantize_intensity(bg_fused(frames, CFG, interpret=True), CFG)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_alpha0_single_frame_squeeze():
    frame = _noisy_stack(1, 45, 55)[0]
    out, carry = temporal_denoise(frame, plan=PLAN, alpha=0.0)
    assert out.shape == frame.shape and carry is None
    ref = quantize_intensity(bg_fused(frame, CFG, interpret=True), CFG)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_warmup_pack_matches_fused_per_frame():
    """alpha > 0 with no history: effective alpha 0 on the fused temporal
    kernel — bit-identical to the per-frame fused path, and must emit a
    carry. The staged oracle (the reference plan) still equals the jnp reference
    exactly, and the fused carry tracks the staged carry."""
    frames = _noisy_stack(3, 45, 55)
    out, carry = temporal_denoise(frames, plan=PLAN, alpha=0.5)
    assert carry.shape == (3,) + carry_shape(45, 55, CFG)
    ref = quantize_intensity(bg_fused(frames, CFG, interpret=True), CFG)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    out_s, carry_s = temporal_denoise(frames, plan=STAGED, alpha=0.5)
    ref_s = jnp.stack([bilateral_grid_filter(frames[i], CFG) for i in range(3)])
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(ref_s))
    np.testing.assert_allclose(
        np.asarray(carry), np.asarray(carry_s), atol=2e-2, rtol=1e-4
    )


def test_blurred_grid_batch_matches_per_frame_reference():
    """The hoisted batched GC+GF (shared cell indices/taps, one batched
    scatter + batched convs) must equal the per-frame staged pipeline
    exactly — it is the definition of the quantity the EMA carries."""
    from repro.core.bilateral_grid import grid_blur, grid_create

    frames = _noisy_stack(4, 33, 47)
    ref = jnp.stack([grid_blur(grid_create(f, CFG), CFG) for f in frames])
    np.testing.assert_array_equal(
        np.asarray(blurred_grid_batch(frames, CFG)), np.asarray(ref)
    )


def test_alpha_validation():
    frames = _noisy_stack(2, 33, 47)
    for bad in (-0.1, 1.0, 1.5):
        with pytest.raises(ValueError):
            temporal_denoise(frames, plan=PLAN, alpha=bad)
    with pytest.raises(ValueError):  # carry/frames stream mismatch
        carry = jnp.zeros((3,) + carry_shape(33, 47, CFG))
        temporal_denoise(frames, plan=PLAN, carry=carry, alpha=0.5)


def test_static_scene_psnr_monotone_in_alpha():
    cfg = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
    clean = synthetic_video(1, 1, 48, 64, motion=0.0)[0]
    vals = []
    for alpha in (0.0, 0.3, 0.6, 0.8):
        packer = MultiStreamPacker(BGPlan(cfg=cfg, mesh=batch_mesh()))
        packer.open(0, alpha=alpha)
        for t in range(12):
            out = packer.pack({0: add_gaussian_noise(clean, 30.0, seed=100 + t)})[0]
        vals.append(float(psnr(clean, out)))
    assert all(b > a for a, b in zip(vals, vals[1:])), vals


def test_packer_no_cross_stream_leak():
    """Stream A denoised in a pack with B must equal A packed alone — the
    stacked carry rows belong to exactly one stream each."""
    cfg = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
    nA = _noisy_stack(5, 40, 56, seed=3)
    nB = _noisy_stack(5, 40, 56, seed=7)
    solo = MultiStreamPacker(BGPlan(cfg=cfg, mesh=batch_mesh()))
    solo.open("A", alpha=0.5)
    solo_out = [solo.pack({"A": nA[t]})["A"] for t in range(5)]
    duo = MultiStreamPacker(BGPlan(cfg=cfg, mesh=batch_mesh()))
    duo.open("A", alpha=0.5)
    duo.open("B", alpha=0.7)
    for t in range(5):
        outs = duo.pack({"A": nA[t], "B": nB[t]})
        np.testing.assert_array_equal(np.asarray(solo_out[t]), np.asarray(outs["A"]))
    assert duo.sessions["A"].frames_seen == duo.sessions["B"].frames_seen == 5


def test_packer_mixed_alpha_and_zero_alpha_carry_free():
    """alpha == 0 sessions never hold a carry and stay bit-identical to the
    fused per-frame path even when packed WITH warm streams (batch
    composition is timing-dependent under the async engine, so cold-stream
    bits must not depend on it); mixed packs still advance the temporal
    sessions; an all-zero-alpha pack is the fused path (no carries
    materialized anywhere)."""
    packer = MultiStreamPacker(PLAN)
    packer.open("warm", alpha=0.6)
    packer.open("cold", alpha=0.0)
    frames = _noisy_stack(2, 33, 47)
    fused_ref = quantize_intensity(bg_fused(frames, CFG, interpret=True), CFG)
    for t in range(2):
        outs = packer.pack({"warm": frames[t], "cold": frames[t]})
        np.testing.assert_array_equal(
            np.asarray(outs["cold"]), np.asarray(fused_ref[t])
        )
    assert packer.sessions["warm"].carry is not None
    assert packer.sessions["cold"].carry is None

    allzero = MultiStreamPacker(PLAN)
    allzero.open(0)
    allzero.open(1)
    out = allzero.pack({0: frames[0], 1: frames[1]})
    ref = quantize_intensity(bg_fused(frames, CFG, interpret=True), CFG)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))
    assert allzero.sessions[0].carry is None


def test_packer_errors():
    packer = MultiStreamPacker(BGPlan(cfg=CFG, mesh=batch_mesh()))
    packer.open("a", alpha=0.2)
    with pytest.raises(ValueError):
        packer.open("a")  # double open
    with pytest.raises(ValueError):
        packer.open("bad", alpha=1.0)  # alpha out of range, session not added
    with pytest.raises(KeyError):
        packer.pack({"ghost": jnp.zeros((24, 24))})
    packer.open("b", alpha=0.2)
    with pytest.raises(ValueError):  # mismatched frame shapes in one pack
        packer.pack({"a": jnp.zeros((24, 24)), "b": jnp.zeros((30, 24))})
    assert packer.pack({}) == {}
    packer.close("b")
    assert packer.live() == 1  # only "a" remains


def test_synthetic_video_fixture():
    a = synthetic_video(5, 4, 40, 60, motion=2.0)
    b = synthetic_video(5, 4, 40, 60, motion=2.0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # deterministic
    assert a.shape == (4, 40, 60)
    # panning: frame 1 shifted by `motion` overlaps frame 0 exactly
    np.testing.assert_array_equal(
        np.asarray(a[1][: 40 - 2, : 60 - 2]), np.asarray(a[0][2:, 2:])
    )
    static = synthetic_video(5, 3, 40, 60, motion=0.0)
    np.testing.assert_array_equal(np.asarray(static[0]), np.asarray(static[2]))
    with pytest.raises(ValueError):
        synthetic_video(0, 0, 40, 60)
