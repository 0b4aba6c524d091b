"""Real-time video denoising on top of the fused bilateral-grid pipeline.

Two layers:

  * :mod:`repro.video.temporal` — the temporal bilateral grid: a recursive
    EMA of the blurred grid carried across frames of one stream
    (``G_t = (1-a) * blur(create(f_t)) + a * G_{t-1}`` before slicing).
    The EMA runs *inside* the fused Pallas kernel for every alpha (the
    blurred planes blend in VMEM right before TI — one kernel dispatch per
    pack, grid never round-tripping HBM), with the stream axis sharded over
    the ``("batch",)`` mesh. ``a == 0`` reduces exactly to the per-frame
    fused path (bit-identical); the staged jnp pipeline is the reference
    oracle, a ``BGPlan(backend="reference")``.
  * :mod:`repro.video.session` — per-stream state (grid carry, frame
    counter) plus a multi-stream packer that batches one frame from each of
    N live streams into one single-dispatch pack (warm/cold/first-frame
    streams mixed via the per-stream alpha vector), carrying the per-stream
    grids as one stacked array.

The async serving front for these lives in ``repro.serving.async_engine``.
"""
from .session import MultiStreamPacker, StreamSession
from .temporal import blurred_grid_batch, carry_shape, temporal_denoise

__all__ = [
    "MultiStreamPacker",
    "StreamSession",
    "blurred_grid_batch",
    "carry_shape",
    "temporal_denoise",
]
