"""Public names for the bilateral-grid Pallas kernels.

``bg_create`` / ``bg_blur`` / ``bg_slice`` are the three staged kernels (the
grid round-trips through HBM between them) and ``bg_fused`` is the single
GC||GF||TI macro-pipeline kernel. Every op auto-selects interpret mode
off-TPU. A whole denoise is dispatched by a :class:`repro.plan.BGPlan`:
``backend="staged"`` chains the staged kernels through :func:`_staged_single`
(vmapped over a batch), ``"fused"`` / ``"fused_streamed"`` run the fused
kernel on the whole batch through its 2-D (batch, stripe) grid.
"""
from __future__ import annotations

from .bg_blur import bg_blur_kernel_call
from .bg_create import bg_create_kernel_call
from .bg_fused import bg_fused_kernel_call
from .bg_slice import bg_slice_kernel_call

__all__ = ["bg_create", "bg_blur", "bg_slice", "bg_fused"]

bg_create = bg_create_kernel_call
bg_blur = bg_blur_kernel_call
bg_slice = bg_slice_kernel_call
bg_fused = bg_fused_kernel_call


def _staged_single(image, cfg, interpret):
    from repro.core.bilateral_grid import grid_normalize

    grid = bg_create_kernel_call(image, cfg, interpret=interpret)
    blurred = bg_blur_kernel_call(grid, cfg, interpret=interpret)
    grid_f = grid_normalize(blurred)
    return bg_slice_kernel_call(grid_f, image, cfg, interpret=interpret)
