"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept across shapes, radii, sigmas, and input dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise, synthetic_image
from repro.core.bilateral_grid import grid_normalize
from repro.kernels import bg_blur, bg_create, bg_fused, bg_slice
from repro.kernels.ref import ref_blur, ref_create, ref_fused, ref_slice
from repro.plan import BGPlan

SHAPES = [(32, 32), (61, 83), (96, 128), (45, 200)]
PARAMS = [
    (2, 2.0, 30.0),
    (4, 8.0, 70.0),
    (7, 4.0, 50.0),
    (12, 8.0, 70.0),
    (16, 8.0, 70.0),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _img(h, w, dtype=jnp.float32, seed=3):
    base = synthetic_image(h, w, seed=seed)
    noisy = add_gaussian_noise(base, 30.0, seed=seed + 1)
    return noisy.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("r,ss,sr", PARAMS)
def test_create_matches_ref(shape, r, ss, sr):
    cfg = BGConfig(r=r, sigma_s=ss, sigma_r=sr)
    img = _img(*shape)
    k = bg_create(img, cfg, interpret=True)
    ref = ref_create(img, cfg)
    assert k.shape == ref.shape
    np.testing.assert_allclose(np.asarray(k), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("shape", SHAPES[:2])
@pytest.mark.parametrize("r,ss,sr", PARAMS)
def test_blur_matches_ref(shape, r, ss, sr):
    cfg = BGConfig(r=r, sigma_s=ss, sigma_r=sr)
    grid = ref_create(_img(*shape), cfg)
    k = bg_blur(grid, cfg, interpret=True)
    ref = ref_blur(grid, cfg)
    np.testing.assert_allclose(np.asarray(k), np.asarray(ref), rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("shape", SHAPES[:2])
@pytest.mark.parametrize("r,ss,sr", PARAMS)
def test_slice_matches_ref(shape, r, ss, sr):
    cfg = BGConfig(r=r, sigma_s=ss, sigma_r=sr)
    img = _img(*shape)
    gf = grid_normalize(ref_blur(ref_create(img, cfg), cfg))
    k = bg_slice(gf, img, cfg, interpret=True)
    ref = ref_slice(gf, img, cfg)
    np.testing.assert_allclose(np.asarray(k), np.asarray(ref), atol=1e-3)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("r,ss,sr", PARAMS)
def test_fused_matches_ref(shape, r, ss, sr):
    cfg = BGConfig(r=r, sigma_s=ss, sigma_r=sr)
    img = _img(*shape)
    k = bg_fused(img, cfg, interpret=True)
    ref = ref_fused(img, cfg)
    np.testing.assert_allclose(np.asarray(k), np.asarray(ref), atol=5e-3)


@pytest.mark.parametrize("dtype", DTYPES)
def test_dtype_sweep_full_pipeline(dtype):
    """bf16 inputs are upcast internally; quantized outputs must agree with
    the float32 path within 1 intensity level."""
    cfg = BGConfig(r=7, sigma_s=4.0, sigma_r=50.0)
    img32 = _img(61, 83, jnp.float32)
    img = img32.astype(dtype)
    plan = BGPlan(cfg=cfg, backend="fused", interpret=True)
    out = plan(img)
    ref = plan(img32)
    diff = np.abs(np.asarray(out) - np.asarray(ref))
    assert np.mean(diff <= 1.0) > 0.99


@pytest.mark.parametrize("fused", [True, False])
def test_pipeline_wrapper_matches_core(fused):
    from repro.core import bilateral_grid_filter

    cfg = BGConfig(r=7, sigma_s=4.0, sigma_r=50.0)
    img = _img(61, 83)
    plan = BGPlan(cfg=cfg, backend="fused" if fused else "staged",
                  interpret=True)
    k = plan(img)
    ref = bilateral_grid_filter(img, cfg)
    diff = np.abs(np.asarray(k) - np.asarray(ref))
    # float-accumulation order differs; quantized outputs may flip 1 LSB rarely
    assert np.mean(diff == 0.0) > 0.995
    assert diff.max() <= 1.0


def test_pow2_weight_mode_kernels():
    cfg = BGConfig(r=8, sigma_s=8.0, sigma_r=70.0, weight_mode="pow2")
    img = _img(48, 64)
    k = bg_fused(img, cfg, interpret=True)
    ref = ref_fused(img, cfg)
    np.testing.assert_allclose(np.asarray(k), np.asarray(ref), atol=5e-3)


def test_kernel_grid_layout_roundtrip():
    """bg_create output layout must be identical to the core grid layout."""
    cfg = BGConfig(r=5, sigma_s=3.0, sigma_r=40.0)
    img = _img(40, 55)
    k = bg_create(img, cfg, interpret=True)
    assert float(jnp.sum(k[..., 0])) == 40 * 55
