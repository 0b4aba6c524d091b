"""Compiled execution plans for the bilateral-grid pipelines (``BGPlan``).

The paper's datapath is *configured once, then streamed*: window radius and
grid geometry fix the FPGA pipeline structure, and frames flow through it at
line rate with no further decisions. This module is the software equivalent:
one plan/compile/execute layer that every caller dispatches through — the
frame engine (``repro.serving.AsyncFrameEngine(plan=)``), the video packer
(``repro.video.MultiStreamPacker(plan)``) and ``temporal_denoise``, the
fleet workers, the data-pipeline frontends, the launchers and the chip
benchmark all take a :class:`BGPlan` and call it (``plan(frames)``; the
temporal form ``plan.as_temporal()(frames, carry=, alpha=)``):

  * :class:`BGPlan` — a frozen, hashable record of **every** dispatch
    decision. Invalid combinations (a temporal carry on the manual-DMA input
    path, a non-"paper" normalization on a kernel backend, a fractional
    ``batch_tile``) are rejected here, once, with a clear error — not deep
    inside a Pallas grid lowering.
  * :func:`plan_for` — heuristics that build a concrete plan from frame
    geometry: ``batch_tile`` and ``stream_input`` are auto-selected from the
    documented VMEM-budget model below.
  * a per-plan compiled-executable cache — every caller of the same plan
    reuses **one** jitted callable (including the shard_map wrapper for
    mesh plans), instead of each layer maintaining its own jit/LRU.

Dispatch-decision table
-----------------------
``BGPlan.backend`` names the compute route; ``temporal`` / ``mesh`` compose
with it:

  backend            route                                       composes with
  ----------------   -----------------------------------------   -------------
  "reference"        vmapped jnp GC->GF->TI (core.bilateral_     temporal
                     grid); the numerical oracle                 (staged EMA)
  "streaming"        lax.scan stripe pipeline (core.streaming,   mesh
                     the paper's Fig. 4 dataflow in jnp)
  "staged"           three staged Pallas kernels, grid through   --
                     HBM between stages (unfused perf baseline)
  "fused"            single GC||GF||TI macro-pipeline Pallas     temporal
                     kernel, grid resident in VMEM               (in-kernel
                                                                 EMA), mesh
  "fused_streamed"   fused kernel + explicit double-buffered     mesh
                     HBM->VMEM input DMA (manual two-slot
                     prefetch instead of automatic pipelining)

``mesh`` (a 1-D device mesh) shards the frame/stream batch axis via
``shard_map`` — pure data parallelism, zero collectives (see
``repro.sharding.bg_shard``); ragged batches are padded and trimmed around
the executable, never inside it. ``temporal`` switches the executable to the
``(frames, carry, alpha) -> (out, new_carry)`` video form.

Storage precision (``BGPlan.precision``)
----------------------------------------
``precision`` names the kernel's *storage* dtype: ``"fp32"`` (default) or
``"bf16"`` — bf16 storage with fp32 accumulation. Under ``"bf16"`` the
fused kernel holds its streamed input stripes, VMEM line buffers, raw and
blurred grid planes, per-step one-hot stacks, and the temporal carry in
bfloat16 while every GC/GF/TI contraction accumulates in float32
(``preferred_element_type``) — halving the per-step VMEM working set (so
``auto_batch_tile`` roughly doubles) and the manual-DMA/HBM bytes the
roofline model charges. The temporal carry is *stored and shipped* in the
plan's storage dtype end-to-end (session state, snapshot wire, socket RPC),
and ``alpha == 0`` bit-identity between the temporal and per-frame paths
holds within each precision mode. Reduced precision is a quality decision:
``plan_for`` defaults to fp32 and only ranks bf16 candidates when asked
(``precision="auto"`` or ``"bf16"``); ``bench_bg_quality`` gates the
bf16-vs-fp32 MSSIM floor. Only ``reference``/``fused``/``fused_streamed``
implement the contract; ``precision="bf16"`` on other backends is rejected
at construction.

The VMEM-budget model (the ``batch_tile`` / ``stream_input`` auto-tuner)
------------------------------------------------------------------------
The fused kernel's per-grid-step working set scales linearly with the batch
tile ``bt`` (frames advanced per macro-pipeline step). Per frame, in
storage-dtype elements (4 B fp32 / 2 B bf16 — see the tensors in
``kernels.bg_fused._pipeline_step``):

  inputs+outputs   6*r*w   default path (2 img + 2 msk + 2 out auto-pipelined
                           blocks), or 4*r*w streamed (2 DMA slots + 2 out —
                           the mask is synthesized in-kernel, never streamed)
  scratch          7*gz*gy + 2*r*w   (three raw planes + blurred plane +
                                     two r-line buffers)
  temporaries      5*r*gz*w   (the GC one-hot z-stack and the TI z one-hots
                              dominate; r*gz is bounded by construction —
                              see kernels.common)

The auto-tuner picks the largest ``bt`` whose step footprint fits
``VMEM_STEP_BUDGET_BYTES`` (half of a 16 MiB VMEM — headroom for compiler
temporaries), capped at ``MAX_AUTO_TILE`` and at the per-device share
``ceil(n_frames / mesh_size)`` when the pack size is known. This replaces the
hand-tuned ``DEFAULT_BATCH_TILE`` and the serve-time ``batch_tile=n_streams``
threading: a 64-stream 60x96 video pack auto-tiles to the whole pack (one
macro-pipeline sweep), a full-HD batch auto-tiles down to a few frames.

``stream_input`` flips on when the *default path's doubled input blocks*
(2 img + 2 msk = 16*r*w bytes per frame-step) exceed
``STREAM_INPUT_THRESHOLD_BYTES``: at paper-scale full-HD radii (r >= 12,
w = 1920) the auto-pipelined input footprint passes 256 KiB per frame and
the plan switches to the manual two-slot DMA path, which halves input HBM
bytes and needs no mask block (the "full-HD blows the auto-pipelining
budget" rule from the PR-2 notes, now code). The temporal path never
streams input (the carry operand claims the manual-DMA slot budget), which
:class:`BGPlan` enforces at construction.

The roofline latency model (how ``plan_for`` ranks candidates)
--------------------------------------------------------------
The VMEM budget above decides which plans are *legal*; :func:`plan_cost`
predicts which legal plan is *fastest*. Per candidate it charges, against
the per-chip peaks of ``repro.launch.hlo_analysis.chip_peaks`` (keyed by
``device_kind``; a host without a TPU ranks for the v5e target):

  compute_s   FLOPs of the GC/GF/TI contractions per stripe step, summed
              over the padded dispatch (``ceil(b_dev/bt) * (ceil(h/r)+2)``
              steps) — the GC one-hot matmul ``4*r*gz*gy*w`` and the TI
              slice contraction ``8*gz*gy*w`` per frame-step dominate.
  memory_s    HBM bytes moved: input blocks (img+msk on the default path,
              img only when streamed — the mask never leaves the kernel),
              the output write-back, and for temporal plans the carry
              read+write (``2 * esz * gx*gy*gz*2`` bytes per frame, where
              ``esz`` is the plan's storage element size: 4 fp32 / 2 bf16).
  overhead_s  ``DISPATCH_OVERHEAD_S`` per dispatch + ``STEP_OVERHEAD_S``
              per grid step (why bigger tiles win: fewer steps) +
              ``STREAM_DMA_OVERHEAD_S`` per frame-step on the manual-DMA
              path (why tiny frames don't stream: the saved mask bytes,
              ``4*r*w / hbm_bw``, must outweigh the DMA issue cost — the
              break-even sits at ``r*w ~ 16k``, reproducing the PR-5
              256 KiB ``auto_stream_input`` rule as a *derived* quantity).

``plan_cost`` sums the three terms (the stripe pipeline serializes DMA
issue and compute within a step in interpret mode; the sum is the
conservative no-overlap bound) — :func:`plan_cost_breakdown` also reports
the classical ``max()`` roofline bound. The model is structural, not
calibrated per host: its job is *ranking* candidates, and measured truth
lives in the plan cache. :func:`plan_cost_hlo` cross-checks it by lowering
a plan's real executable and running the optimized HLO through
``launch.hlo_cost.analyze_hlo`` / ``launch.hlo_analysis.roofline_terms``.

Plan resolution order: ``plan_for`` consults the on-disk measured-plan
cache (:mod:`repro.plan_cache`, written by ``benchmarks/bench_plan_sweep``)
first, then ranks the legal ``backend x batch_tile`` candidates by
``plan_cost``; pinned kwargs skip both. ``BGPlan.provenance`` records
which route produced the plan (``"cache"``/``"model"``/``"explicit"``/
``"default"``) so bench rows and serving logs stay attributable.

A plan built directly (``BGPlan(cfg=..., backend=...)``) with ``batch_tile``
``None`` defers the tile to the kernel's ``DEFAULT_BATCH_TILE``.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bilateral_grid import (
    BGConfig,
    bilateral_grid_filter,
    grid_normalize,
    grid_shape,
    grid_slice,
    quantize_intensity,
)

__all__ = [
    "BGPlan",
    "plan_for",
    "plan_cost",
    "plan_cost_breakdown",
    "plan_cost_hlo",
    "auto_batch_tile",
    "auto_stream_input",
    "step_bytes_per_frame",
    "PRECISIONS",
    "precision_bytes",
    "set_dispatch_hook",
    "VMEM_STEP_BUDGET_BYTES",
    "STREAM_INPUT_THRESHOLD_BYTES",
    "MAX_AUTO_TILE",
    "DISPATCH_OVERHEAD_S",
    "STEP_OVERHEAD_S",
    "STREAM_DMA_OVERHEAD_S",
]

BACKENDS = ("reference", "streaming", "staged", "fused", "fused_streamed")
_KERNEL_BACKENDS = ("staged", "fused", "fused_streamed")
_FUSED_BACKENDS = ("fused", "fused_streamed")
_MESH_BACKENDS = ("streaming", "fused", "fused_streamed")
_TEMPORAL_BACKENDS = ("reference", "fused")

# Storage precisions and the backends that implement the bf16-storage /
# fp32-accumulate contract (module docstring, "Storage precision"). The
# element sizes feed the VMEM-budget and roofline models.
PRECISIONS = ("fp32", "bf16")
_BF16_BACKENDS = ("reference", "fused", "fused_streamed")
_PRECISION_BYTES = {"fp32": 4, "bf16": 2}


def precision_bytes(precision: str) -> int:
    """Storage element size in bytes for a plan precision name."""
    try:
        return _PRECISION_BYTES[precision]
    except KeyError:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}"
        ) from None

# The auto-tuner's budget model (documented in the module docstring): keep
# the fused kernel's per-step working set within half a 16 MiB VMEM, switch
# to the manual-DMA input path when the doubled auto-pipelined input blocks
# alone pass 256 KiB per frame-step, and never tile past MAX_AUTO_TILE
# (per-step latency stops amortizing anything beyond that).
VMEM_STEP_BUDGET_BYTES = 8 * 2**20
STREAM_INPUT_THRESHOLD_BYTES = 256 * 2**10
MAX_AUTO_TILE = 64

# Latency-model overhead constants (module docstring, "roofline latency
# model"). Structural, not per-host-calibrated: they set the *break-even
# points* of the ranking — STREAM_DMA_OVERHEAD_S puts the stream-vs-default
# crossover at r*w ~ 16k (4*r*w/hbm_bw saved bytes vs the DMA issue cost,
# matching the PR-5 256 KiB rule), STEP_OVERHEAD_S makes fewer-bigger tiles
# win whenever VMEM allows. Measured truth belongs in the plan cache.
DISPATCH_OVERHEAD_S = 30e-6
STEP_OVERHEAD_S = 2e-6
STREAM_DMA_OVERHEAD_S = 8e-8


# ---------------------------------------------------------------- heuristics
def step_bytes_per_frame(
    cfg: BGConfig, h: int, w: int, *, stream_input: bool = False,
    temporal: bool = False, precision: str = "fp32",
) -> int:
    """Fused-kernel per-grid-step VMEM bytes for ONE frame of the batch tile.

    The linear-in-``bt`` part of the step footprint (io blocks + scratch +
    dominant temporaries); constants (column one-hots, taps) are tile-
    independent and excluded. Temporal plans additionally hold the
    double-buffered carry in/out blocks (``2 * 2 * (2*gz*gy)`` elements
    per frame — one ``(gy, gz, 2)`` carry plane each way). Every term is
    held in the plan's *storage* dtype (4 B fp32 / 2 B bf16: the one-hot
    z-stacks and interpolation weights are materialized in bf16 too — the
    contractions consume bf16 operands and accumulate fp32). See the module
    docstring for the term-by-term derivation.
    """
    r = cfg.r
    _, gy, gz = grid_shape(h, w, cfg)
    io = (4 if stream_input else 6) * r * w
    scratch = 7 * gz * gy + 2 * r * w
    temporaries = 5 * r * gz * w
    carry = 8 * gz * gy if temporal else 0
    return precision_bytes(precision) * (io + scratch + temporaries + carry)


def auto_stream_input(cfg: BGConfig, h: int, w: int) -> bool:
    """True when the default path's doubled input blocks (2 img + 2 msk =
    16*r*w bytes per frame-step) exceed the auto-pipelining threshold."""
    return 16 * cfg.r * w > STREAM_INPUT_THRESHOLD_BYTES


def auto_batch_tile(
    cfg: BGConfig,
    h: int,
    w: int,
    n_frames: Optional[int] = None,
    *,
    stream_input: bool = False,
    mesh_size: int = 1,
    temporal: bool = False,
    precision: str = "fp32",
) -> int:
    """Largest batch tile whose per-step working set fits the VMEM budget.

    Capped at ``MAX_AUTO_TILE`` and, when the pack size is known, at the
    per-device share ``ceil(n_frames / mesh_size)`` (a larger tile would be
    pure padding on every device). bf16 storage halves the per-frame step
    bytes, so the feasible tile roughly doubles.
    """
    per = step_bytes_per_frame(
        cfg, h, w, stream_input=stream_input, temporal=temporal,
        precision=precision,
    )
    bt = max(1, VMEM_STEP_BUDGET_BYTES // per)
    bt = min(bt, MAX_AUTO_TILE)
    if n_frames is not None:
        bt = min(bt, -(-int(n_frames) // max(1, mesh_size)))
    return int(max(1, bt))


# --------------------------------------------------------- roofline cost model
def plan_cost_breakdown(plan: "BGPlan", h: int, w: int,
                        n_frames: Optional[int] = None) -> dict:
    """Term-by-term roofline latency estimate for dispatching ``plan`` on an
    ``(n_frames, h, w)`` batch — the model behind :func:`plan_cost` (see the
    module docstring for the derivation). All device-rate terms use the
    per-chip peaks from ``repro.launch.hlo_analysis`` and describe ONE mesh
    device's shard (devices run the shard in parallel).

    Returns a dict: ``flops``, ``hbm_bytes``, ``steps``, ``compute_s``,
    ``memory_s``, ``overhead_s``, ``bound_s`` (the classical
    ``max(compute, memory)`` roofline bound), and ``total_s`` (the
    no-overlap sum that ranks candidates).
    """
    from repro.launch.hlo_analysis import chip_peaks

    cfg = plan.cfg
    r = cfg.r
    gx, gy, gz = grid_shape(h, w, cfg)
    b = 1 if n_frames is None else max(1, int(n_frames))
    b_dev = -(-b // plan.mesh_size)  # per-device shard

    if plan.backend in _FUSED_BACKENDS:
        streamed = plan.backend == "fused_streamed"
        bt = plan.tile_for(b)  # plan tile (or DEFAULT_BATCH_TILE) clamped
        nb = -(-b_dev // bt)
        b_pad = nb * bt
        # grid steps: ceil(h/r) stripes + 2 macro-pipeline warm-up/drain
        # stages, + 1 extra TI drain step for temporal when h % r == 0
        n_grid = -(-h // r) + 2 + (1 if plan.temporal and h % r == 0 else 0)
        steps = nb * n_grid
        # FLOPs per frame-step: GC one-hot matmul + TI slice contraction
        # dominate; elementwise one-hot/weight build and the GF blur trail
        per_frame_step_flops = (
            4 * r * gz * gy * w      # GC einsum "bcizw,wg->bcizg"
            + 8 * gz * gy * w        # TI einsum "pbzg,cwg->pbzcw"
            + 10 * r * gz * w        # one-hot z-stack + weights + blend
            + 30 * gz * gy           # separable 3-tap GF blur, 2 channels
        )
        flops = b_pad * n_grid * per_frame_step_flops
        # HBM traffic: img (+ msk on the default path) in, out back; the
        # grid itself never leaves VMEM on the fused path. Operand blocks
        # travel in the plan's storage dtype (bf16 halves them).
        esz = precision_bytes(plan.precision)
        frame_bytes = esz * r * n_grid * w
        hbm = b_pad * frame_bytes * (2 if streamed else 3)
        if plan.temporal:
            hbm += 2 * esz * b_pad * gx * gy * gz * 2  # carry read + write
        overhead = DISPATCH_OVERHEAD_S + steps * STEP_OVERHEAD_S
        if streamed:
            overhead += b_pad * n_grid * STREAM_DMA_OVERHEAD_S
    else:
        # Oracle backends (reference / streaming / staged): rough structural
        # charges — enough to rank them behind a legal fused plan, never
        # used to split hairs between oracles.
        grid_elems = gx * gy * gz * 2
        flops = b_dev * (100 * h * w + 60 * grid_elems)
        hbm = 4 * b_dev * (2 * h * w + 10 * grid_elems)
        steps = b_dev * (-(-h // r)) if plan.backend == "streaming" else b_dev
        overhead = DISPATCH_OVERHEAD_S * (
            3 if plan.backend == "staged" else 1
        ) + steps * STEP_OVERHEAD_S
        if plan.temporal:
            hbm += 2 * 4 * b_dev * grid_elems

    peaks = chip_peaks()
    compute_s = flops / peaks["peak_flops"]
    memory_s = hbm / peaks["hbm_bw"]
    return {
        "flops": float(flops),
        "hbm_bytes": float(hbm),
        "steps": int(steps),
        "compute_s": compute_s,
        "memory_s": memory_s,
        "overhead_s": overhead,
        "bound_s": max(compute_s, memory_s),
        "total_s": compute_s + memory_s + overhead,
    }


def plan_cost(plan: "BGPlan", h: int, w: int,
              n_frames: Optional[int] = None) -> float:
    """Predicted seconds to dispatch ``plan`` on ``(n_frames, h, w)`` frames
    (the no-overlap roofline sum; see :func:`plan_cost_breakdown`). This is
    the ranking key :func:`plan_for` minimizes over legal candidates."""
    return plan_cost_breakdown(plan, h, w, n_frames)["total_s"]


def plan_cost_hlo(plan: "BGPlan", h: int, w: int, n_frames: int = 1):
    """Measured-structure cross-check of :func:`plan_cost`: lower + compile
    the plan's real executable for the given geometry and run the optimized
    HLO through ``launch.hlo_cost.analyze_hlo`` (trip-count-correct FLOPs /
    HBM / collective bytes) into ``launch.hlo_analysis.roofline_terms``.
    Returns the :class:`repro.launch.hlo_analysis.Roofline`. Slower than the
    analytic model (a full XLA compile) — sweep/diagnostic use, not the
    ``plan_for`` hot path."""
    from repro.launch.hlo_analysis import roofline_terms
    from repro.sharding.bg_shard import row_pad

    n_frames = int(n_frames) + row_pad(plan.mesh_size, int(n_frames))
    frames = jax.ShapeDtypeStruct((n_frames, int(h), int(w)), jnp.float32)
    fn = plan.executable()
    if plan.temporal:
        gx, gy, gz = grid_shape(h, w, plan.cfg)
        carry = jax.ShapeDtypeStruct(
            (int(n_frames), gx, gy, gz, 2), plan.storage_dtype
        )
        alpha = jax.ShapeDtypeStruct((int(n_frames),), jnp.float32)
        lowered = fn.lower(frames, carry, alpha)
    else:
        lowered = fn.lower(frames)
    compiled = lowered.compile()
    hlo_text = compiled.as_text()
    return roofline_terms({}, hlo_text)


# -------------------------------------------------------------------- BGPlan
@dataclasses.dataclass(frozen=True)
class BGPlan:
    """One frozen, hashable record of every bilateral-grid dispatch decision.

    Fields:
      cfg:             the grid/window configuration (frozen ``BGConfig``).
      backend:         compute route — see the module-docstring table.
      temporal:        the executable takes ``(frames, carry, alpha)`` and
                       returns ``(out, new_carry)`` (video grid-EMA). Only
                       ``"fused"`` (in-kernel EMA) and ``"reference"`` (the
                       staged jnp oracle) support it.
      batch_tile:      frames per fused-kernel grid step. ``None`` defers to
                       the kernel's ``DEFAULT_BATCH_TILE``; :func:`plan_for`
                       fills in a concrete auto-tuned value. Ignored
                       (normalized to ``None``) by non-fused backends.
      mesh:            1-D device mesh sharding the frame/stream batch axis,
                       or ``None`` for single-device dispatch. Size-1 meshes
                       normalize to ``None``.
      quantize_output: apply the paper's output rounding at the exit.
      interpret:       Pallas interpret-mode override (``None`` = auto:
                       interpret everywhere except real TPUs). On a TPU
                       backend only a caller's own ``interpret=True`` runs
                       the kernels interpreted; a deserialized plan that
                       carries it is refused (:meth:`from_json`).
      precision:       storage dtype contract — ``"fp32"`` (default) or
                       ``"bf16"`` (bf16 storage / fp32 accumulate; see the
                       module docstring). Only ``reference`` / ``fused`` /
                       ``fused_streamed`` implement it.

    Equal plans (``==``/``hash``) share one compiled executable via
    :meth:`executable`; calling the plan dispatches through it.
    """

    cfg: BGConfig
    backend: str = "fused"
    temporal: bool = False
    batch_tile: Optional[int] = None
    mesh: Optional[jax.sharding.Mesh] = None
    quantize_output: bool = True
    interpret: Optional[bool] = None
    precision: str = "fp32"

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {BACKENDS}"
            )
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"unknown precision {self.precision!r}; expected one of "
                f"{PRECISIONS}"
            )
        if self.precision == "bf16" and self.backend not in _BF16_BACKENDS:
            raise ValueError(
                f"precision='bf16' is implemented by backends "
                f"{_BF16_BACKENDS}; backend {self.backend!r} has no "
                f"storage-precision contract"
            )
        bt = self.batch_tile
        if bt is not None:
            if isinstance(bt, bool) or not isinstance(bt, int):
                raise ValueError(
                    f"batch_tile must be a positive int or None, got "
                    f"{bt!r} ({type(bt).__name__}) — a fractional tile "
                    f"surfaces as an opaque Pallas grid error"
                )
            if bt < 1:
                raise ValueError(f"batch_tile must be >= 1, got {bt}")
            if self.backend not in _FUSED_BACKENDS:
                # the staged oracle / reference paths have no tiling concept;
                # normalize so plan equality doesn't split their exec cache
                object.__setattr__(self, "batch_tile", None)
        if self.backend in _KERNEL_BACKENDS and self.cfg.normalize_mode != "paper":
            raise ValueError(
                "kernel backends implement the paper normalization mode "
                f"(got normalize_mode={self.cfg.normalize_mode!r})"
            )
        if self.temporal:
            if self.backend == "fused_streamed":
                raise ValueError(
                    "stream_input does not compose with a temporal carry "
                    "(the carry operand owns the manual-DMA slot budget); "
                    "use backend='fused'"
                )
            if self.backend not in _TEMPORAL_BACKENDS:
                raise ValueError(
                    f"temporal plans support backends {_TEMPORAL_BACKENDS}, "
                    f"got {self.backend!r}"
                )
        if self.mesh is not None:
            if len(self.mesh.axis_names) != 1:
                raise ValueError(
                    f"BGPlan meshes are 1-D batch meshes, got axes "
                    f"{self.mesh.axis_names!r}"
                )
            if int(self.mesh.devices.size) == 1:
                object.__setattr__(self, "mesh", None)  # degrade to plain
            elif self.backend not in _MESH_BACKENDS:
                raise ValueError(
                    f"backend {self.backend!r} does not shard over a mesh; "
                    f"mesh plans need one of {_MESH_BACKENDS}"
                )

    # ------------------------------------------------------------ utilities
    @property
    def mesh_size(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    @property
    def storage_dtype(self):
        """The jnp storage dtype the precision contract names: what the
        kernel's operand blocks, scratch, and the temporal carry are held
        (and shipped) in. Accumulation is always fp32."""
        return jnp.bfloat16 if self.precision == "bf16" else jnp.float32

    @property
    def np_storage_dtype(self) -> np.dtype:
        """Numpy view of :attr:`storage_dtype` (snapshot / wire side)."""
        return np.dtype(self.storage_dtype)

    def tile_for(self, n_frames: int) -> int:
        """Effective fused-kernel tile for an ``n_frames`` pack: the plan's
        own tile (``plan_for``'s auto-tuned value, or the kernel's
        ``DEFAULT_BATCH_TILE`` when the plan defers) clamped to the
        per-device shard, exactly as the kernel clamps it. This is what the
        video packer asks per pack instead of being handed ``batch_tile=``
        — pinning the clamp in the plan keeps the dispatch geometry (and
        therefore the temporal-carry bits) an explicit plan decision."""
        from repro.kernels.bg_fused import DEFAULT_BATCH_TILE

        shard = -(-int(n_frames) // self.mesh_size)
        tile = DEFAULT_BATCH_TILE if self.batch_tile is None else self.batch_tile
        return max(1, min(tile, shard))

    def with_tile(self, batch_tile: int) -> "BGPlan":
        """This plan with ``batch_tile`` pinned (cached — per-pack hot path)."""
        if batch_tile == self.batch_tile:
            return self
        return _tiled_variant(self, batch_tile)

    def with_options(self, **changes) -> "BGPlan":
        """``dataclasses.replace`` with plan validation re-run."""
        return dataclasses.replace(self, **changes)

    def as_temporal(self, temporal: bool = True) -> "BGPlan":
        """The temporal / per-frame variant of this plan (cached — the video
        packer derives one per pack, on the dispatch hot path)."""
        if self.temporal == temporal:
            return self
        return _temporal_variant(self, temporal)

    def fallback_ladder(self) -> Tuple["BGPlan", ...]:
        """The degradation ladder for fault-tolerant serving: this plan
        first, then progressively simpler-but-sturdier variants
        (``fused_streamed -> fused -> reference``; any other backend falls
        straight to ``reference``). Each rung drops the machinery most
        likely to be implicated in a kernel-backend failure — the manual
        DMA first, the Pallas kernel second — and the final rung is the
        vmapped jnp oracle, which runs anywhere XLA does. ``reference``
        rungs shed the mesh (it does not shard) and their ``batch_tile``
        normalizes away; ``temporal`` survives every rung (both ``fused``
        and ``reference`` carry the grid EMA). Consumed by
        ``repro.reliability.retry.GuardedDispatch``."""
        ladder = [self]
        if self.backend == "fused_streamed":
            ladder.append(self.with_options(backend="fused"))
        if self.backend != "reference":
            ladder.append(
                self.with_options(backend="reference", mesh=None, batch_tile=None)
            )
        return tuple(ladder)

    # -------------------------------------------------------- serialization
    def to_json(self) -> dict:
        """JSON-serializable payload capturing every dispatch decision.

        The mesh itself is a device object and does not serialize; its
        *size* does, and :meth:`from_json` rebuilds an equivalent 1-D batch
        mesh on the loading host (which is the fleet-distribution contract:
        a controller ships decisions, workers bind their own devices).
        """
        return {
            "version": 1,
            "cfg": dataclasses.asdict(self.cfg),
            "backend": self.backend,
            "temporal": self.temporal,
            "batch_tile": self.batch_tile,
            "mesh_size": self.mesh_size,
            "quantize_output": self.quantize_output,
            "interpret": self.interpret,
            "precision": self.precision,
        }

    @classmethod
    def from_json(cls, data: dict, *, mesh="auto") -> "BGPlan":
        """Rebuild a plan from :meth:`to_json` output. ``mesh="auto"``
        recreates a 1-D batch mesh of the serialized ``mesh_size`` (raising
        if this host lacks the devices — a silently-shrunk mesh would shift
        the dispatch geometry the hash vouches for); pass an explicit mesh
        (or ``None`` for single-device) to override."""
        if int(data.get("version", 1)) != 1:
            raise ValueError(
                f"unknown BGPlan serialization version {data.get('version')!r}"
            )
        if data.get("interpret") and jax.default_backend() == "tpu":
            # interpret mode is a host-local choice: a plan shipped from a
            # CPU host must not silently run its kernels interpreted here
            raise ValueError(
                "serialized plan pins Pallas interpret mode, but this host "
                "compiles its kernels for the TPU; resolve the plan with "
                "interpret=None, or construct it here with interpret=True"
            )
        if mesh == "auto":
            ms = int(data.get("mesh_size", 1))
            if ms <= 1:
                mesh = None
            else:
                if jax.device_count() < ms:
                    raise ValueError(
                        f"serialized plan wants a {ms}-device mesh but only "
                        f"{jax.device_count()} device(s) are visible; pass "
                        f"mesh= explicitly to rebind"
                    )
                from repro.sharding.bg_shard import batch_mesh

                mesh = batch_mesh(ms)
        return cls(
            cfg=BGConfig(**data["cfg"]),
            backend=data["backend"],
            temporal=bool(data.get("temporal", False)),
            batch_tile=data.get("batch_tile"),
            mesh=mesh,
            quantize_output=bool(data.get("quantize_output", True)),
            interpret=data.get("interpret"),
            precision=data.get("precision", "fp32"),
        )

    def plan_hash(self) -> str:
        """Stable hex digest of the serialized plan — the compatibility
        check the plan cache and fleet controller compare (two hosts agree
        on a dispatch recipe iff their plan hashes match)."""
        payload = json.dumps(self.to_json(), sort_keys=True,
                             separators=(",", ":"))
        return hashlib.sha256(payload.encode()).hexdigest()[:16]

    @property
    def provenance(self) -> str:
        """How this plan was chosen: ``"cache"`` (measured-plan cache hit),
        ``"model"`` (roofline-ranked by ``plan_for``), ``"explicit"``
        (``plan_for`` with every free decision pinned by the caller), or
        ``"default"`` (constructed directly — kernel-default tiling).
        Informational — not part of plan equality or the hash."""
        return self.__dict__.get("_provenance", "default")

    def describe(self) -> str:
        """One-line dispatch summary for bench rows and serving logs."""
        return (
            f"backend={self.backend} bt={self.batch_tile} "
            f"mesh={self.mesh_size} temporal={int(self.temporal)} "
            f"prec={self.precision} src={self.provenance}"
        )

    # ------------------------------------------------------------- dispatch
    def executable(self):
        """The plan's compiled callable (one per equal plan, cached).

        Non-temporal: ``fn(frames) -> out``. Temporal:
        ``fn(frames, carry, alpha) -> (out, new_carry)``. The instance memo
        skips the (hash-based) global cache lookup on the dispatch hot path;
        equal plans still resolve to the same callable through
        ``_plan_executable``.
        """
        fn = self.__dict__.get("_exec_memo")
        if fn is None:
            fn = _plan_executable(self)
            object.__setattr__(self, "_exec_memo", fn)
        return fn

    def __call__(self, frames, carry=None, alpha=None):
        if _DISPATCH_HOOK is not None:
            # host-side pre-dispatch hook (fault injection / tracing); see
            # set_dispatch_hook — a raised exception aborts this dispatch
            _DISPATCH_HOOK(self)
        frames = jnp.asarray(frames)
        if self.temporal:
            if carry is None or alpha is None:
                raise ValueError(
                    "temporal plan dispatch needs both carry= and alpha="
                )
            squeeze = frames.ndim == 2
            if squeeze:
                frames = frames[None]
                carry = jnp.asarray(carry)[None]
            if frames.ndim != 3:
                raise ValueError(
                    f"temporal plans take (h, w) or (n, h, w) frames, got "
                    f"{frames.shape}"
                )
            n = frames.shape[0]
            if not isinstance(alpha, jax.Array):
                # host-side alpha: broadcast + range-check here, once (a
                # device-resident alpha vector is trusted — checking it
                # would force a sync on the dispatch hot path)
                alpha_np = np.broadcast_to(
                    np.asarray(alpha, np.float32), (n,)
                )
                if (alpha_np < 0.0).any() or (alpha_np >= 1.0).any():
                    raise ValueError(
                        f"temporal alpha must be in [0, 1), got {alpha}"
                    )
                alpha = jnp.asarray(alpha_np)
            elif alpha.ndim == 0:
                alpha = jnp.broadcast_to(alpha, (n,))
            out, new_carry = self._run(frames, carry, alpha)
            return (out[0], new_carry[0]) if squeeze else (out, new_carry)
        if carry is not None or alpha is not None:
            raise ValueError(
                "carry/alpha require a temporal plan (BGPlan(temporal=True))"
            )
        if frames.ndim == 4:
            # color (b, h, w, c): per-channel grids, channels folded into the
            # batch axis (frames and channels are equally independent)
            b, h, w, c = frames.shape
            folded = jnp.moveaxis(frames, -1, 1).reshape(b * c, h, w)
            out = self._run(folded)
            return jnp.moveaxis(out.reshape(b, c, h, w), 1, -1)
        if frames.ndim not in (2, 3):
            raise ValueError(
                f"expected (h, w), (b, h, w) or (b, h, w, c) frames, got "
                f"{frames.shape}"
            )
        if self.mesh is not None and frames.ndim == 2:
            return self._run(frames[None])[0]  # one frame: a batch of one
        return self._run(frames)

    def _run(self, *arrays):
        """The executable on ``arrays``; on a mesh through
        :func:`_mesh_call`, which pads and trims the batch around it."""
        if self.mesh is None:
            return self.executable()(*arrays)
        return _mesh_call(self.executable(), self.mesh_size, *arrays)


# ------------------------------------------------------------------ plan_for
def _stamp(plan: BGPlan, provenance: str) -> BGPlan:
    object.__setattr__(plan, "_provenance", provenance)
    return plan


# The batch-tile candidate grid the model ranks: powers of two below the
# VMEM cap, plus the cap itself (the old heuristic's pick, so the model can
# never do worse than "largest legal").
_TILE_LADDER = (1, 2, 4, 8, 16, 32, 64)


def plan_for(
    cfg: BGConfig,
    height: int,
    width: int,
    *,
    n_frames: Optional[int] = None,
    temporal: bool = False,
    backend: Optional[str] = None,
    sharded: Optional[bool] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_tile: Optional[int] = None,
    stream_input: Optional[bool] = None,
    quantize_output: bool = True,
    interpret: Optional[bool] = None,
    precision: Optional[str] = None,
    cache=None,
) -> BGPlan:
    """Build a concrete :class:`BGPlan` for the given frame geometry.

    Free decisions (``backend`` within the fused family via
    ``stream_input``, ``batch_tile``) are resolved in order: the on-disk
    measured-plan cache (:mod:`repro.plan_cache`; ``cache=None`` uses the
    process default, a :class:`~repro.plan_cache.PlanCache` pins one,
    ``False`` disables the lookup), then the roofline latency model
    (:func:`plan_cost`) ranking every legal candidate under the VMEM
    budget. Pass explicit values to pin decisions and skip both; the
    result's :attr:`BGPlan.provenance` records which route won.

    ``precision`` is *opt-in reduced precision*: ``None`` (the default)
    keeps every candidate fp32 — a numerics decision must never be made
    silently on the caller's behalf — ``"fp32"``/``"bf16"`` pin it, and
    ``"auto"`` lets the model rank bf16 candidates against fp32 on the
    fused family (bf16 halves step bytes, so its VMEM-feasible tiles are
    roughly twice as large; exact-cost ties keep fp32).

    ``sharded=None`` auto-meshes over all local devices when more than one
    is present *and* the resolved backend shards (the single-host oracle
    backends — ``reference``/``staged`` — simply stay single-device);
    ``sharded=False`` forces single-device, ``True`` requires a
    mesh-capable backend and builds the mesh; explicit ``mesh`` wins.
    ``temporal=True`` returns the video-form plan (fused in-kernel
    grid-EMA; never input-streamed).
    """
    if precision not in (None, "auto") and precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {(None, 'auto') + PRECISIONS}, got "
            f"{precision!r}"
        )
    fully_auto = (
        backend is None
        and stream_input is None
        and batch_tile is None
        and precision in (None, "auto")
    )
    if backend is None:
        if temporal:
            if stream_input:
                raise ValueError(
                    "stream_input does not compose with a temporal carry"
                )
            candidates = ("fused",)
        elif stream_input is None:
            candidates = ("fused", "fused_streamed")
        else:
            candidates = ("fused_streamed",) if stream_input else ("fused",)
    else:
        if (
            stream_input is not None
            and (backend == "fused_streamed") != bool(stream_input)
            and backend in _FUSED_BACKENDS
        ):
            raise ValueError(
                f"stream_input={stream_input} contradicts backend={backend!r}"
            )
        candidates = (backend,)

    mesh_capable = all(b in _MESH_BACKENDS for b in candidates)
    if sharded and not mesh_capable:
        raise ValueError(
            f"sharded=True needs a mesh-capable backend {_MESH_BACKENDS}, "
            f"got {backend!r}"
        )
    if sharded is False:
        mesh = None
    elif mesh is None and mesh_capable and jax.device_count() > 1:
        # auto-mesh only for backends that shard; an *explicit* mesh on an
        # oracle backend falls through to BGPlan's construction error
        from repro.sharding.bg_shard import batch_mesh

        mesh = batch_mesh()
    if mesh is not None and int(mesh.devices.size) == 1:
        mesh = None
    mesh_size = 1 if mesh is None else int(mesh.devices.size)

    if batch_tile is not None and mesh_size > 1 and n_frames is not None:
        shard = -(-int(n_frames) // mesh_size)
        if batch_tile > shard:
            raise ValueError(
                f"batch_tile={batch_tile} exceeds the {shard} frame(s) each "
                f"of the {mesh_size} mesh devices receives for "
                f"n_frames={n_frames}; the kernel would silently clamp the "
                f"tile (shifting the temporal-carry dispatch geometry) — "
                f"use batch_tile<={shard} or batch_tile=None (auto)"
            )

    def build(be, bt, prec="fp32"):
        return BGPlan(
            cfg=cfg,
            backend=be,
            temporal=temporal,
            batch_tile=bt,
            mesh=mesh,
            quantize_output=quantize_output,
            interpret=interpret,
            precision=prec,
        )

    fused_family = all(b in _FUSED_BACKENDS for b in candidates)
    # The precision candidate axis: fp32-only unless the caller opted in.
    # "auto" only widens the grid on the fused family — ranking an oracle
    # backend's precision by a cost model that cannot tell them apart would
    # be noise, and pinned "bf16" on a non-implementing backend surfaces as
    # BGPlan's construction error below.
    if precision == "bf16":
        precisions = ("bf16",)
    elif precision == "auto" and fused_family:
        precisions = ("fp32", "bf16")
    else:
        precisions = ("fp32",)

    no_freedom = (
        len(candidates) == 1
        and len(precisions) == 1
        and (batch_tile is not None or not fused_family)
    )
    if no_freedom:
        # every decision pinned (or an oracle backend with none to make)
        return _stamp(
            build(candidates[0], batch_tile, precisions[0]), "explicit"
        )

    # ---- measured-plan cache (fully-auto calls only: a cached entry is a
    # complete decision and must not override a pinned kwarg)
    if fully_auto and cache is not False:
        from repro.plan_cache import get_default_cache, workload_key

        pc = get_default_cache() if cache is None else cache
        ent = pc.lookup(
            workload_key(cfg, height, width, n_frames, temporal, mesh_size)
        )
        if ent is not None:
            try:
                pj = ent["plan"]
                be, bt = pj["backend"], pj.get("batch_tile")
                prec = pj.get("precision", "fp32")
                # a cached bf16 winner must not leak into a caller that did
                # not opt into reduced precision (precision is a numerics
                # decision, not just a latency one)
                ok = be in candidates and prec in precisions
                if (
                    ok
                    and bt is not None
                    and mesh_size > 1
                    and n_frames is not None
                ):
                    ok = bt <= -(-int(n_frames) // mesh_size)
                if ok:
                    return _stamp(build(be, bt, prec), "cache")
            except (KeyError, TypeError, ValueError):
                pass  # stale/incompatible entry: fall through to the model

    # ---- roofline-model ranking over the legal candidate grid
    plans = []
    for prec in precisions:
        for be in candidates:
            if batch_tile is not None:
                tiles = [batch_tile]
            else:
                cap = auto_batch_tile(
                    cfg,
                    height,
                    width,
                    n_frames,
                    stream_input=be == "fused_streamed",
                    mesh_size=mesh_size,
                    temporal=temporal,
                    precision=prec,
                )
                tiles = sorted({t for t in _TILE_LADDER if t < cap} | {cap})
            plans.extend(build(be, t, prec) for t in tiles)
    n_eval = (
        int(n_frames)
        if n_frames is not None
        else max(p.batch_tile for p in plans)
    )
    best = min(
        plans,
        key=lambda p: (
            plan_cost(p, height, width, n_eval),
            p.precision != "fp32",  # exact tie: precision costs quality
            p.backend != "fused",  # exact tie: no reason to pay the DMA path
            -p.batch_tile,
        ),
    )
    return _stamp(best, "model")


@functools.lru_cache(maxsize=256)
def _temporal_variant(plan: BGPlan, temporal: bool) -> BGPlan:
    return dataclasses.replace(plan, temporal=temporal)


@functools.lru_cache(maxsize=256)
def _tiled_variant(plan: BGPlan, batch_tile: int) -> BGPlan:
    return dataclasses.replace(plan, batch_tile=batch_tile)


# --------------------------------------------------------- dispatch hook
# One process-wide host-side hook run at the top of every BGPlan.__call__,
# before any device work. The integration point for fault injection
# (repro.reliability.faults.FaultInjector.plan_hook installs one that can
# raise InjectedFault) and for dispatch tracing; None (the default) costs a
# single global load per dispatch.
_DISPATCH_HOOK = None


def set_dispatch_hook(hook):
    """Install ``hook(plan)`` as the global pre-dispatch hook; returns the
    previous hook (restore it when done — see ``FaultInjector.plan_hook``
    for the context-managed form). Pass ``None`` to clear."""
    global _DISPATCH_HOOK
    prev = _DISPATCH_HOOK
    _DISPATCH_HOOK = hook
    return prev


def _shard_map(inner, mesh, n_in: int, n_out: int):
    """``inner`` over ``mesh`` with plain batch-axis specs on every input
    and output (``check_vma=False`` — pallas_call has no varying-axes rule).
    The batch must already be a multiple of the device count: each device
    runs ``inner`` on its own rows, and nothing crosses the mesh."""
    from jax.sharding import PartitionSpec as P

    spec = P(mesh.axis_names[0])
    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=spec if n_in == 1 else (spec,) * n_in,
        out_specs=spec if n_out == 1 else (spec,) * n_out,
        check_vma=False,
    )


def _mesh_call(exe, n_devices: int, *arrays):
    """A mesh plan's dispatch: zero-pad every input's leading axis to a
    multiple of ``n_devices``, run the executable ``exe``, and trim every
    output back to the original length. Padding and trim stay outside the
    executable, so it compiles once per padded length and holds no
    collective. A batch already at a multiple passes through untouched and
    its answer stays sharded ``P("batch")``: the engine places its batches
    so (``repro.serving.device_stages.place_rows``)."""
    from repro.sharding.bg_shard import pad_rows, row_pad

    n = arrays[0].shape[0]
    pad = row_pad(n_devices, n)
    if not pad:
        return exe(*arrays)
    out = exe(*(pad_rows(a, pad) for a in arrays))
    if isinstance(out, tuple):
        return tuple(o[:n] for o in out)
    return out[:n]


# -------------------------------------------------- compiled-executable cache
@functools.lru_cache(maxsize=256)
def _plan_executable(plan: BGPlan):
    """ONE jitted callable per plan (the compiled-executable cache).

    The callable owns the complete dispatch: dtype normalization, the
    shard_map wrapper for mesh plans, the kernel/scan/reference compute,
    tile-padding trim, and output quantization — so repeat dispatches of a
    plan hit one compiled executable regardless of which layer (pipeline,
    engine, packer, launcher) issued them. Compositions mirror the pre-plan
    routes operation-for-operation (``tests/test_plan.py`` holds them
    bit-identical). A mesh plan's executable takes a batch at a multiple of
    the device count, sharded ``P("batch")`` or placed by it, and returns it
    so; :meth:`BGPlan.__call__` pads and trims other lengths around it
    (:func:`_mesh_call`).

    The executable is named by its backend (its module reads
    ``jit_bg_<backend>``, with ``_temporal`` for temporal plans), and the
    fused backends run their stages under ``jax.named_scope``:
    ``bg.relayout`` (cast, pad, reshape and carry transpose before the
    kernel), ``bg.kernel``, ``bg.trim`` (padding cut, carry layout back) and
    ``bg.quantize``, so a profiler trace names every device operation.
    They take the frames in their own dtype: the kernel sees 8-bit frames
    as levels and runs grid creation in one bf16 pass
    (``repro.kernels.bg_fused.is_level_dtype``).
    """
    fn = _executable_fn(plan)
    fn.__name__ = fn.__qualname__ = (
        f"bg_{plan.backend}" + ("_temporal" if plan.temporal else "")
    )
    return jax.jit(fn)


def _executable_fn(plan: BGPlan):
    """The Python function that :func:`_plan_executable` jits."""
    cfg = plan.cfg
    quant = plan.quantize_output
    interpret = plan.interpret
    batch_tile = plan.batch_tile
    mesh = plan.mesh

    def _maybe_quantize(out):
        with jax.named_scope("bg.quantize"):
            out = out.astype(jnp.float32)
            return quantize_intensity(out, cfg) if quant else out

    # ------------------------------------------------------------- temporal
    if plan.temporal:
        if plan.backend == "reference":
            # the staged jnp oracle: grid visible between GF and TI. Under
            # bf16 the oracle *stores* (frames, carry out) in bf16 and
            # accumulates fp32, mirroring the fused kernel's contract; the
            # fp32 path is byte-for-byte the pre-precision jaxpr (every
            # added astype is a same-dtype no-op).
            from repro.video.temporal import blurred_grid_batch

            sdt = plan.storage_dtype

            def fn(frames, carry, alpha):
                frames = frames.astype(jnp.float32)
                if plan.precision == "bf16":
                    frames = frames.astype(sdt).astype(jnp.float32)
                blurred = blurred_grid_batch(frames, cfg)
                a = alpha.astype(jnp.float32).reshape((-1, 1, 1, 1, 1))
                new_carry = (1.0 - a) * blurred + a * carry.astype(
                    jnp.float32
                )
                grid_f = grid_normalize(new_carry)
                out = jax.vmap(lambda gf, f: grid_slice(gf, f, cfg))(
                    grid_f, frames
                )
                return _maybe_quantize(out), new_carry.astype(sdt)

            return fn

        # the unjitted impl: traced directly into this plan's one executable
        # (a nested pjit costs ~10% dispatch time in interpret mode)
        from repro.kernels.bg_fused import bg_fused_impl

        def inner_temporal(frames, carry, alpha):
            return bg_fused_impl(
                frames,
                cfg,
                interpret=interpret,
                batch_tile=batch_tile,
                carry=carry,
                alpha=alpha,
                precision=plan.precision,
            )

        if mesh is None:

            def fn(frames, carry, alpha):
                out, new_carry = inner_temporal(frames, carry, alpha)
                return _maybe_quantize(out), new_carry

            return fn

        meshed = _shard_map(inner_temporal, mesh, n_in=3, n_out=2)

        def fn(frames, carry, alpha):
            out, new_carry = meshed(frames, carry, alpha)
            return _maybe_quantize(out), new_carry

        return fn

    # --------------------------------------------------------- non-temporal
    if plan.backend == "reference":
        bf16 = plan.precision == "bf16"

        def fn(frames):
            if bf16:
                # storage emulation: round the frames the kernel would hold
                # in bf16; the filter itself accumulates fp32 as always
                frames = (
                    frames.astype(jnp.float32)
                    .astype(jnp.bfloat16)
                    .astype(jnp.float32)
                )
            single = lambda im: bilateral_grid_filter(
                im, cfg, quantize_output=quant
            )
            if frames.ndim == 3:
                return jax.vmap(single)(frames)
            return single(frames)

        return fn

    if plan.backend == "streaming":
        from repro.core.streaming import _streaming_single

        single = functools.partial(
            _streaming_single, cfg=cfg, quantize_output=quant
        )

        if mesh is None:

            def fn(frames):
                if frames.ndim == 3:
                    return jax.vmap(single)(frames)
                return single(frames)

            return fn

        return _shard_map(
            lambda x: jax.vmap(single)(x), mesh, n_in=1, n_out=1
        )

    if plan.backend == "staged":
        from repro.kernels.ops import _staged_single

        def fn(frames):
            frames = frames.astype(jnp.float32)
            if frames.ndim == 3:
                out = jax.vmap(
                    lambda im: _staged_single(im, cfg, interpret)
                )(frames)
            else:
                out = _staged_single(frames, cfg, interpret)
            return _maybe_quantize(out)

        return fn

    # fused / fused_streamed — the unjitted impl, traced into one executable
    from repro.kernels.bg_fused import bg_fused_impl

    inner = functools.partial(
        bg_fused_impl,
        cfg=cfg,
        interpret=interpret,
        batch_tile=batch_tile,
        stream_input=plan.backend == "fused_streamed",
        precision=plan.precision,
    )

    if mesh is None:

        def fn(frames):
            return _maybe_quantize(inner(frames))

        return fn

    meshed = _shard_map(inner, mesh, n_in=1, n_out=1)

    def fn(frames):
        return _maybe_quantize(meshed(frames))

    return fn


def executable_cache_info():
    """Cache statistics of the per-plan compiled-executable cache."""
    return _plan_executable.cache_info()
