"""End-to-end system behaviour: train -> checkpoint -> resume -> serve, and
the paper's denoiser running inside the data pipeline."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.bg_denoise import PAPER_DEFAULT, TABLE1_SWEEP
from repro.configs.registry import get_smoke_config
from repro.core import (
    BGConfig,
    add_gaussian_noise,
    bilateral_grid_filter,
    mssim,
    synthetic_image,
)
from repro.data import lm_batches, spectrogram_denoise, vlm_preprocess
from repro.plan import BGPlan
from repro.serving import Request, ServeEngine
from repro.train import OptConfig, Trainer


def test_train_checkpoint_serve_roundtrip():
    """The full lifecycle on one config: a few train steps, checkpoint,
    resume into a serving engine, generate deterministically."""
    cfg = get_smoke_config("yi-6b")
    opt = OptConfig(lr=1e-3, warmup_steps=2, decay_steps=20)
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(cfg, opt, d, ckpt_every=5)
        tr.init_or_resume()
        batches = (
            {k: jnp.asarray(v) for k, v in b.items()}
            for b in lm_batches(cfg.vocab_size, 4, 16, 10, seed=3)
        )
        tr.run(batches, max_steps=10)

        tr2 = Trainer(cfg, opt, d)
        assert tr2.init_or_resume() == "resumed" and tr2.step == 10
        eng = ServeEngine(cfg, tr2.params, max_slots=2, max_len=48)
        reqs = [Request(uid=i, prompt=[1 + i, 2, 3], max_tokens=5) for i in range(2)]
        for r in reqs:
            assert eng.submit(r)
        eng.run_to_completion()
        assert all(len(r.generated) == 5 for r in reqs)
        # same params, same prompts => deterministic outputs
        eng2 = ServeEngine(cfg, tr2.params, max_slots=2, max_len=48)
        reqs2 = [Request(uid=i, prompt=[1 + i, 2, 3], max_tokens=5) for i in range(2)]
        for r in reqs2:
            eng2.submit(r)
        eng2.run_to_completion()
        assert [r.generated for r in reqs] == [r.generated for r in reqs2]


def test_bg_denoise_in_data_pipeline():
    """The paper's technique as a pipeline stage: batched denoise improves
    MSSIM for every image in the batch; VLM preprocessing runs end to end."""
    clean = jnp.stack([synthetic_image(64, 96, seed=i) for i in range(3)])
    noisy = jnp.stack(
        [add_gaussian_noise(clean[i], 30.0, seed=10 + i) for i in range(3)]
    )
    plan = BGPlan(cfg=BGConfig(r=4, sigma_s=3.0, sigma_r=50.0),
                  backend="reference")
    den = plan(noisy)
    for i in range(3):
        assert float(mssim(clean[i], den[i])) > float(mssim(clean[i], noisy[i]))
    ctx = vlm_preprocess(noisy, plan, patch=16, dim=32)
    assert ctx.shape == (3, (64 // 16) * (96 // 16), 32)
    assert bool(jnp.all(jnp.isfinite(ctx)))
    spec = spectrogram_denoise(noisy)  # the [audio] stage's default plan
    assert spec.shape == noisy.shape and bool(jnp.all(jnp.isfinite(spec)))


def test_paper_workload_presets():
    """The paper's own configs are well-formed and runnable at reduced size."""
    assert PAPER_DEFAULT.bg.r == 12 and PAPER_DEFAULT.height == 1080
    assert tuple(w.bg.r for w in TABLE1_SWEEP) == (4, 8, 12, 16)
    img = add_gaussian_noise(synthetic_image(60, 80), 30.0)
    out = bilateral_grid_filter(img, TABLE1_SWEEP[0].bg)
    assert out.shape == (60, 80)
