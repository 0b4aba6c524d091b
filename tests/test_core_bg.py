"""Core bilateral-grid behaviour: paper-claim validation + implementation
equivalences (batch == streaming == fixed-point within LSB)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BGConfig,
    add_gaussian_noise,
    bilateral_filter,
    bilateral_grid_filter,
    bilateral_grid_filter_fixed,
    grid_blur,
    grid_create,
    grid_shape,
    mssim,
    psnr,
    synthetic_image,
)
from repro.plan import BGPlan

H, W = 96, 128
IMG = synthetic_image(H, W)
NOISY = add_gaussian_noise(IMG, 30.0)


def cfg(r=7, ss=4.0, sr=50.0, **kw):
    return BGConfig(r=r, sigma_s=ss, sigma_r=sr, **kw)


# ---------------------------------------------------------------- grid basics
def test_grid_shape_matches_paper_formula():
    c = cfg(r=12, ss=8.0, sr=70.0)
    gx, gy, gz = grid_shape(1080, 1920, c)
    assert (gx, gy) == (1080 // 12 + 2, 1920 // 12 + 2)
    assert gz == int(np.floor(255.0 / (12 * 70.0 / 8.0))) + 2


def test_grid_create_conservation():
    """Sum of counts == #pixels; sum of sums == sum of image (mass is moved,
    never created)."""
    c = cfg()
    g = grid_create(NOISY, c)
    assert float(jnp.sum(g[..., 0])) == H * W
    np.testing.assert_allclose(
        float(jnp.sum(g[..., 1])), float(jnp.sum(NOISY)), rtol=1e-6
    )


def test_grid_blur_preserves_mass():
    """With zero-padded borders the 3^3 blur only loses mass at the (empty)
    boundary planes; interior mass is weighted identically for both channels."""
    c = cfg()
    g = grid_create(NOISY, c)
    b = grid_blur(g, c)
    # blur weights are positive; counts stay positive wherever they were
    assert float(jnp.min(b)) >= 0.0
    # both channels blurred with identical taps: ratio bounded by intensities
    ratio = b[..., 1] / jnp.maximum(b[..., 0], 1e-12)
    assert float(jnp.max(ratio)) <= 255.0 + 1e-3


# ------------------------------------------------------- output-quality claims
def test_bg_denoises():
    out = bilateral_grid_filter(NOISY, cfg())
    assert float(mssim(IMG, out)) > float(mssim(IMG, NOISY)) + 0.2


def test_bg_matches_bf_quality_band():
    """Fig. 12: with proper parameters the BG reaches BF-equivalent MSSIM."""
    out_bg = bilateral_grid_filter(NOISY, cfg())
    out_bf = bilateral_filter(NOISY, 7, 4.0, 50.0)
    m_bg = float(mssim(IMG, out_bg))
    m_bf = float(mssim(IMG, out_bf))
    assert m_bg > m_bf - 0.05, (m_bg, m_bf)


def test_bg_output_range():
    out = bilateral_grid_filter(NOISY, cfg())
    assert float(jnp.min(out)) >= 0.0 and float(jnp.max(out)) <= 255.0


def test_constant_image_fixed_point():
    """A constant image is a fixed point of any bilateral filter."""
    flat = jnp.full((64, 64), 131.0)
    for mode in ("paper", "classic"):
        out = bilateral_grid_filter(flat, cfg(normalize_mode=mode))
        np.testing.assert_allclose(np.asarray(out), 131.0)


def test_classic_vs_paper_normalization_close():
    a = bilateral_grid_filter(NOISY, cfg(normalize_mode="paper"), quantize_output=False)
    b = bilateral_grid_filter(NOISY, cfg(normalize_mode="classic"), quantize_output=False)
    # same filter up to the normalization-order approximation
    assert float(jnp.mean(jnp.abs(a - b))) < 10.0


# ----------------------------------------------------- implementation parity
def _streaming(img, c):
    """The stripe-streaming scan (Fig. 4 dataflow), unquantized."""
    return BGPlan(cfg=c, backend="streaming", quantize_output=False)(img)


@pytest.mark.parametrize("mode", ["paper", "classic"])
@pytest.mark.parametrize("r", [2, 5, 7, 12])
def test_streaming_equals_batch(mode, r):
    c = cfg(r=r, normalize_mode=mode)
    batch = bilateral_grid_filter(NOISY, c, quantize_output=False)
    stream = _streaming(NOISY, c)
    np.testing.assert_allclose(np.asarray(stream), np.asarray(batch), atol=1e-3)


def test_streaming_non_multiple_height():
    img = NOISY[: H - 5]
    c = cfg(r=7)
    batch = bilateral_grid_filter(img, c, quantize_output=False)
    stream = _streaming(img, c)
    np.testing.assert_allclose(np.asarray(stream), np.asarray(batch), atol=1e-3)


@pytest.mark.parametrize("r", [4, 8, 12, 16])
def test_fixed_point_matches_pow2_float(r):
    """Shift-only integer datapath agrees with pow2-float within 1 LSB
    almost everywhere (quantization of interp coefficients)."""
    cf = cfg(r=r, ss=8.0, sr=70.0, weight_mode="pow2")
    ref = bilateral_grid_filter(NOISY, cf)
    fx = bilateral_grid_filter_fixed(NOISY, cf)
    diff = np.abs(np.asarray(ref) - np.asarray(fx))
    assert np.mean(diff <= 1.0) > 0.99, np.mean(diff)
    assert diff.max() <= 4.0


def test_pow2_weights_quality_close_to_float():
    """Paper claim: shift-only arithmetic does not hurt denoising quality."""
    m_float = float(mssim(IMG, bilateral_grid_filter(NOISY, cfg())))
    m_pow2 = float(
        mssim(IMG, bilateral_grid_filter(NOISY, cfg(weight_mode="pow2")))
    )
    assert abs(m_float - m_pow2) < 0.05


# --------------------------------------------------------------------- metrics
def test_mssim_identity_and_symmetry():
    assert float(mssim(IMG, IMG)) == pytest.approx(1.0, abs=1e-5)
    assert float(mssim(IMG, NOISY)) == pytest.approx(float(mssim(NOISY, IMG)), abs=1e-5)
    assert float(mssim(IMG, NOISY)) < 0.9


def test_psnr_identity():
    assert float(psnr(IMG, IMG)) > 100.0
    assert 5.0 < float(psnr(IMG, NOISY)) < 30.0


def test_bf_reference_properties():
    """BF sanity: constant image fixed-point; denoises; stays in range."""
    flat = jnp.full((48, 48), 77.0)
    np.testing.assert_allclose(np.asarray(bilateral_filter(flat, 5, 3.0, 40.0)), 77.0)
    out = bilateral_filter(NOISY, 7, 4.0, 50.0)
    assert float(mssim(IMG, out)) > float(mssim(IMG, NOISY))
    assert float(jnp.min(out)) >= 0.0 and float(jnp.max(out)) <= 255.0
