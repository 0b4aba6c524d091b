"""Pallas TPU kernels for the paper's hot spots (GC / GF / TI / fused)."""
from .ops import bg_blur, bg_create, bg_fused, bg_slice

__all__ = ["bg_blur", "bg_create", "bg_fused", "bg_slice"]
