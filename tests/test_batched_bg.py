"""Batched throughput path validation.

The fused kernel's (batch, stripe) grid must be invisible numerically:
  * every frame of a batch matches the pure-jnp oracle `ref.ref_fused`,
    including ragged shapes (h % r != 0, w % r != 0);
  * the degenerate b == 1 batch is bit-identical to the single-frame path;
  * batch-tile padding (b not divisible by the tile) never leaks the zero
    padding frames into real outputs;
  * batched plans (staged and fused kernels, streaming scan, reference)
    agree with the same plans on each frame alone.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
from repro.kernels import bg_fused
from repro.kernels.ref import ref_fused
from repro.plan import BGPlan

# ragged shapes: every (shape, r) pair has h % r != 0 and w % r != 0
RAGGED = [
    ((61, 83), 7),
    ((45, 200), 6),
    ((33, 47), 4),
]


def _batch(b, h, w, seed=0):
    return add_gaussian_noise(synthetic_batch(b, h, w, seed=seed), 30.0, seed=seed + 50)


@pytest.mark.parametrize("shape,r", RAGGED)
@pytest.mark.parametrize("b", [1, 3])
def test_batched_fused_matches_ref_ragged(shape, r, b):
    h, w = shape
    assert h % r != 0 and w % r != 0  # the matrix is genuinely ragged
    cfg = BGConfig(r=r, sigma_s=4.0, sigma_r=60.0)
    imgs = _batch(b, h, w)
    out = bg_fused(imgs, cfg, interpret=True)
    assert out.shape == (b, h, w)
    for i in range(b):
        ref = ref_fused(imgs[i], cfg)
        err = float(jnp.max(jnp.abs(out[i] - ref)))
        assert err <= 1e-4, f"frame {i}: max abs err {err}"


@pytest.mark.parametrize("shape,r", RAGGED)
def test_degenerate_batch_bitwise_single_frame(shape, r):
    """b == 1 must be bit-identical to the (h, w) single-frame call."""
    h, w = shape
    cfg = BGConfig(r=r, sigma_s=4.0, sigma_r=60.0)
    img = _batch(1, h, w)[0]
    single = bg_fused(img, cfg, interpret=True)
    batched = bg_fused(img[None], cfg, interpret=True)
    assert batched.shape == (1, h, w)
    np.testing.assert_array_equal(np.asarray(batched[0]), np.asarray(single))


def test_batch_tile_padding_is_masked():
    """b=5 with tile=2 pads to 6 frames; padding must not perturb any frame
    (each tile sweeps its own grid steps, so results stay bit-identical)."""
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    imgs = _batch(5, 40, 55)
    out = bg_fused(imgs, cfg, interpret=True, batch_tile=2)
    for i in range(5):
        np.testing.assert_array_equal(
            np.asarray(out[i]), np.asarray(bg_fused(imgs[i], cfg, interpret=True))
        )


@pytest.mark.parametrize("fused", [True, False])
def test_batched_pipeline_wrapper_matches_per_frame(fused):
    cfg = BGConfig(r=7, sigma_s=4.0, sigma_r=50.0)
    imgs = _batch(3, 45, 64)
    plan = BGPlan(cfg=cfg, backend="fused" if fused else "staged",
                  interpret=True)
    out = plan(imgs)
    assert out.shape == imgs.shape
    for i in range(3):
        ref = plan(imgs[i])
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(ref))


def test_batched_streaming_matches_per_frame():
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    imgs = _batch(3, 40, 55)
    plan = BGPlan(cfg=cfg, backend="streaming")
    out = plan(imgs)
    assert out.shape == imgs.shape
    for i in range(3):
        ref = plan(imgs[i])
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(ref), atol=1e-5
        )


def test_denoise_batch_kernel_path():
    """A fused plan feeds the batch natively to the fused kernel and stays
    within 1 quantized level of the vmapped jnp reference plan."""
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    imgs = _batch(2, 40, 55)
    out_k = BGPlan(cfg=cfg, backend="fused")(imgs)
    out_j = BGPlan(cfg=cfg, backend="reference")(imgs)
    diff = np.abs(np.asarray(out_k) - np.asarray(out_j))
    assert np.mean(diff == 0.0) > 0.995
    assert diff.max() <= 1.0


@pytest.mark.parametrize("shape,r", RAGGED)
@pytest.mark.parametrize("b", [1, 3])
def test_stream_input_bitwise_default_path(shape, r, b):
    """The explicit double-buffered HBM->VMEM input path must be numerically
    invisible: bit-identical to the automatically pipelined default, including
    ragged shapes, batch-tile padding and the drain steps."""
    h, w = shape
    cfg = BGConfig(r=r, sigma_s=4.0, sigma_r=60.0)
    imgs = _batch(b, h, w)
    base = bg_fused(imgs, cfg, interpret=True, batch_tile=2)
    stream = bg_fused(imgs, cfg, interpret=True, batch_tile=2, stream_input=True)
    np.testing.assert_array_equal(np.asarray(stream), np.asarray(base))


def test_stream_input_single_frame_squeeze():
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    img = _batch(1, 40, 55)[0]
    np.testing.assert_array_equal(
        np.asarray(bg_fused(img, cfg, interpret=True, stream_input=True)),
        np.asarray(bg_fused(img, cfg, interpret=True)),
    )


@pytest.mark.parametrize("kernel", [True, False])
def test_color_frames_fold_channels_into_batch(kernel):
    """(b, h, w, 3) color batches denoise per channel by folding the channel
    axis into the batch axis; round-trips bit-exactly against denoising each
    channel plane separately (fused kernel plan, and the reference plan)."""
    cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
    plan = BGPlan(cfg=cfg, backend="fused" if kernel else "reference")
    base = _batch(3, 40, 55)
    # three genuinely different channel planes per frame
    color = jnp.stack(
        [base, jnp.flip(base, axis=1), jnp.flip(base, axis=2)], axis=-1
    )
    out = plan(color)
    assert out.shape == color.shape
    per_channel = jnp.stack([plan(color[..., c]) for c in range(3)], axis=-1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(per_channel))
