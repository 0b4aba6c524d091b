"""Compiled-HLO analysis: collective bytes + the three roofline terms.

cost_analysis() provides FLOPs/bytes; collective traffic is NOT in
cost_analysis, so we parse the post-SPMD optimized HLO text and sum the
shaped operands of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (async -start variants counted once).

Hardware model: per-chip peaks keyed by ``device_kind`` (``chip_peaks``).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

__all__ = [
    "PEAKS",
    "TARGET_KIND",
    "chip_peaks",
    "collective_stats",
    "roofline_terms",
    "Roofline",
]

# Per-chip peaks keyed by jax's ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of ICI over four links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# The chip this repo targets: a host with no TPU attached (CPU tests, the
# described-topology compiles) models this one.
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: Optional[str] = None) -> dict:
    """Peaks of ``device_kind``; by default the first local device's kind on
    a TPU host, and ``TARGET_KIND`` on a host without one. A kind that is not
    in ``PEAKS`` raises ``KeyError``: an unknown chip never silently gets
    another chip's numbers."""
    if device_kind is None:
        import jax

        dev = jax.devices()[0]
        device_kind = dev.device_kind if dev.platform == "tpu" else TARGET_KIND
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak numbers for device kind {device_kind!r}; add its "
            f"published peaks to repro.launch.hlo_analysis.PEAKS"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# `%x = f32[8,16]{1,0} all-reduce(...)` or tuple outputs
_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|ragged-all-to-all|"
    r"collective-permute)(-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, dict]:
    """Per-kind {count, bytes} where bytes = sum of result-shape bytes (the
    tensor being moved, per device)."""
    out: Dict[str, dict] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, kind, _ = m.groups()
        b = _shape_bytes(shape_txt)
        d = out.setdefault(kind, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += b
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_chip: float
    hbm_bytes_per_chip: float
    collective_bytes_per_chip: float
    collective_breakdown: dict
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops_per_chip,
            "hbm_bytes_per_chip": self.hbm_bytes_per_chip,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "collective_breakdown": self.collective_breakdown,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def roofline_terms(cost: dict, hlo_text: str) -> Roofline:
    """Terms per the assignment:
       compute    = HLO_FLOPs / (chips * peak)   [costs are per-chip for the
                    SPMD module, so this is flops_per_chip / peak]
       memory     = HLO_bytes / (chips * HBM_bw)
       collective = collective_bytes / (chips * link_bw)

    XLA's cost_analysis counts while (scan) bodies once, so FLOPs/bytes/
    collectives come from the structural model in hlo_cost (trip-count-
    correct); the raw cost dict is kept by the caller for reference.
    """
    from .hlo_cost import analyze_hlo

    hc = analyze_hlo(hlo_text)
    peaks = chip_peaks()
    return Roofline(
        flops_per_chip=hc.flops,
        hbm_bytes_per_chip=hc.hbm_bytes,
        collective_bytes_per_chip=hc.collective_bytes,
        collective_breakdown=hc.collectives,
        compute_s=hc.flops / peaks["peak_flops"],
        memory_s=hc.hbm_bytes / peaks["hbm_bw"],
        collective_s=hc.collective_bytes / peaks["ici_bw"],
    )
