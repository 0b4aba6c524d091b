"""The paper's technique as a first-class LM-framework feature: BG denoising
as the [vlm] image-frontend preprocessing stage (DESIGN.md
§Arch-applicability), feeding patch embeddings to the llama-3.2-vision
cross-attention layers.

Run:  PYTHONPATH=src python examples/vlm_preprocess.py
"""
import jax
import jax.numpy as jnp

from repro.configs.bg_denoise import PAPER_DEFAULT
from repro.configs.registry import get_smoke_config
from repro.core import BGConfig, add_gaussian_noise, mssim, synthetic_image
from repro.data import vlm_preprocess
from repro.models import forward, init_params
from repro.plan import plan_for


def main():
    cfg = get_smoke_config("llama-3.2-vision-11b")
    B, patch = 2, 14
    h, w = 126, 126  # 9x9 patches

    clean = jnp.stack([synthetic_image(h, w, seed=i) for i in range(B)])
    noisy = jnp.stack(
        [add_gaussian_noise(clean[i], 30.0, seed=i) for i in range(B)]
    )
    bg = BGConfig(r=4, sigma_s=3.0, sigma_r=50.0)

    # the denoiser dispatch is a compiled plan: fused Pallas kernel with an
    # auto-tuned batch tile (and mesh sharding on a multi-device host)
    bg_plan = plan_for(bg, h, w, n_frames=B)

    ctx_noisy = vlm_preprocess(noisy, None, patch, cfg.d_model)
    ctx_clean = vlm_preprocess(clean, None, patch, cfg.d_model)
    ctx_denoised = vlm_preprocess(noisy, bg_plan, patch, cfg.d_model)
    # denoising must pull patch embeddings toward the clean ones
    d_noisy = float(jnp.mean(jnp.abs(ctx_noisy - ctx_clean)))
    d_denoised = float(jnp.mean(jnp.abs(ctx_denoised - ctx_clean)))
    print(f"patch-embedding distance to clean: noisy {d_noisy:.4f} -> "
          f"BG-denoised {d_denoised:.4f}")
    for i in range(B):
        print(f"  image {i} MSSIM noisy vs clean: "
              f"{float(mssim(clean[i], noisy[i])):.3f}")

    # pad/trim context to the smoke config's cross_attn token count
    n = cfg.cross_attn_tokens
    ctx = ctx_denoised[:, :n]
    if ctx.shape[1] < n:
        ctx = jnp.pad(ctx, ((0, 0), (0, n - ctx.shape[1]), (0, 0)))

    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 12), 0, cfg.vocab_size)
    logits, _, _ = forward(params, cfg, tokens=tokens, cross_ctx=ctx, mode="train")
    print(f"VLM forward with BG-denoised image context: logits {logits.shape}, "
          f"finite={bool(jnp.all(jnp.isfinite(logits)))}")


if __name__ == "__main__":
    main()
