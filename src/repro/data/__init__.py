from .synthetic import (
    TokenStream,
    audio_frames,
    lm_batches,
    synthetic_video,
    vision_context,
)
from .pipeline import patchify_embed, spectrogram_denoise, vlm_preprocess
