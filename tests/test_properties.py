"""Property-based tests (hypothesis) for the system's invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is not part of the pinned container image; skip (don't fail
# collection) where it is unavailable rather than adding a dependency.
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (
    BGConfig,
    bilateral_filter,
    bilateral_grid_filter,
    bilateral_grid_filter_fixed,
    grid_create,
    mssim,
)
from repro.plan import BGPlan

SETTINGS = dict(max_examples=20, deadline=None)


def _image(draw, hmin=8, hmax=40):
    h = draw(st.integers(hmin, hmax))
    w = draw(st.integers(hmin, hmax))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, 256, size=(h, w)).astype(np.float32)
    )


images = st.composite(_image)
radii = st.integers(1, 8)
sigmas_s = st.floats(0.5, 16.0, allow_nan=False)
sigmas_r = st.floats(5.0, 120.0, allow_nan=False)


@given(images(), radii, sigmas_s, sigmas_r)
@settings(**SETTINGS)
def test_grid_mass_conservation(img, r, ss, sr):
    c = BGConfig(r=r, sigma_s=ss, sigma_r=sr)
    g = grid_create(img, c)
    assert float(jnp.sum(g[..., 0])) == img.shape[0] * img.shape[1]
    np.testing.assert_allclose(
        float(jnp.sum(g[..., 1])), float(jnp.sum(img)), rtol=1e-5
    )


@given(images(), radii, sigmas_s, sigmas_r)
@settings(**SETTINGS)
def test_classic_mode_output_within_input_range(img, r, ss, sr):
    """Homogeneous normalization is a convex combination of cell averages."""
    c = BGConfig(r=r, sigma_s=ss, sigma_r=sr, normalize_mode="classic")
    out = bilateral_grid_filter(img, c, quantize_output=False)
    assert float(jnp.min(out)) >= float(jnp.min(img)) - 1e-2
    assert float(jnp.max(out)) <= float(jnp.max(img)) + 1e-2


@given(images(), radii, sigmas_s, sigmas_r)
@settings(**SETTINGS)
def test_paper_mode_output_in_intensity_range(img, r, ss, sr):
    c = BGConfig(r=r, sigma_s=ss, sigma_r=sr)
    out = bilateral_grid_filter(img, c)
    assert float(jnp.min(out)) >= 0.0 and float(jnp.max(out)) <= 255.0
    # quantized output is integral
    arr = np.asarray(out)
    np.testing.assert_array_equal(arr, np.floor(arr))


@given(
    st.integers(0, 255).map(float),
    st.integers(12, 40),
    st.integers(12, 40),
    radii,
    sigmas_s,
    sigmas_r,
)
@settings(**SETTINGS)
def test_constant_image_invariance(level, h, w, r, ss, sr):
    """Any bilateral-type filter must leave constant images untouched.

    Known paper-mode sensitivity (admitted in the paper's conclusion and
    reproduced here): when sigma_g = sigma_s/r is tiny the 3^3 blur taps
    underflow, neighbor z-cells stay empty, eq. (4) zeroes them, and TI leaks
    toward 0. The invariance therefore only holds for paper-mode when the
    blur actually populates the 1-neighborhood; classic mode and the BF are
    unconditionally invariant.
    """
    img = jnp.full((h, w), level)
    c_classic = BGConfig(r=r, sigma_s=ss, sigma_r=sr, normalize_mode="classic")
    np.testing.assert_allclose(
        np.asarray(bilateral_grid_filter(img, c_classic)), level, atol=0
    )
    # Paper-mode invariance needs the 3^3 blur to populate even the diagonal
    # (1,1,1) neighbors above the empty-cell threshold: tap^3 = e^{-3/(2 sg^2)}
    # >= 1e-12 requires sigma_g = ss/r >= ~0.25. Below that, eq. (4) zeroes
    # diagonal corners and TI leaks toward 0 — the sensitivity the paper's
    # conclusion admits.
    if ss / r >= 0.25:
        c_paper = BGConfig(r=r, sigma_s=ss, sigma_r=sr, normalize_mode="paper")
        np.testing.assert_allclose(
            np.asarray(bilateral_grid_filter(img, c_paper)), level, atol=0
        )
    np.testing.assert_allclose(
        np.asarray(bilateral_filter(img, min(r, 5), ss, sr)), level, atol=0
    )


@given(images(hmin=10, hmax=32), radii, sigmas_s, sigmas_r)
@settings(max_examples=10, deadline=None)
def test_streaming_equals_batch_property(img, r, ss, sr):
    c = BGConfig(r=r, sigma_s=ss, sigma_r=sr)
    a = bilateral_grid_filter(img, c, quantize_output=False)
    b = BGPlan(cfg=c, backend="streaming", quantize_output=False)(img)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


@given(images(), st.integers(1, 6), sigmas_s, sigmas_r)
@settings(**SETTINGS)
def test_bf_output_within_input_range(img, r, ss, sr):
    out = bilateral_filter(img, r, ss, sr, quantize_output=False)
    assert float(jnp.min(out)) >= float(jnp.min(img)) - 1e-3
    assert float(jnp.max(out)) <= float(jnp.max(img)) + 1e-3


@given(images(hmin=16, hmax=32), st.integers(2, 16), sigmas_s, sigmas_r)
@settings(**SETTINGS)
def test_fixed_point_integer_range(img, r, ss, sr):
    c = BGConfig(r=r, sigma_s=ss, sigma_r=sr, weight_mode="pow2")
    out = np.asarray(bilateral_grid_filter_fixed(img, c))
    assert out.min() >= 0 and out.max() <= 255
    np.testing.assert_array_equal(out, np.floor(out))


@given(images(hmin=16, hmax=32))
@settings(**SETTINGS)
def test_mssim_bounds(img):
    assert float(mssim(img, img)) > 0.9999
    other = 255.0 - img
    v = float(mssim(img, other))
    assert -1.0 <= v <= 1.0
