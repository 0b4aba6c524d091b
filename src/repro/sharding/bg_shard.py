"""Batch-axis device sharding for the fused bilateral-grid service path.

Frames are independent, so the TPU analogue of the paper's "add more
pipeline stages" is pure data parallelism: a 1-D ``batch`` mesh where each
device runs the whole fused GC||GF||TI macro-pipeline on its slice of the
frame batch. The same holds for the *temporal* video path
(:func:`bg_temporal_sharded`): the per-stream grid carry and alpha rows
shard with their stream's frame, so each device advances its streams' EMAs
locally. Nothing in the kernel reads across frames. The contract:

  * a mesh plan's executable takes a batch whose length is a multiple of
    the device count, sharded (or shardable) ``P("batch")`` on the frame
    axis, and returns ``P("batch")``; the constant operands (column
    one-hots, taps) are rebuilt inside the per-shard call and live
    replicated in each device's VMEM;
  * the executable holds no cross-device collective — no psum, no
    ppermute, no all-gather, for any batch length — because it neither pads
    nor trims: a ragged batch is padded with zero rows to a device multiple
    before it (:func:`row_pad`, :func:`pad_rows`) and trimmed after it, by
    ``repro.plan._mesh_call``; each shard then pads independently to its
    batch tile, exactly as the single-device call does, so the output is
    bit-identical to the single-device ``bg_fused_kernel_call`` on the same
    batch;
  * the serving engine places each micro-batch itself
    (``repro.serving.device_stages.place_rows``): its rows go straight to
    the chip of their shard, all shards at once, and the pad rows are
    made there; each answer row is split off on that chip
    (``split_shards``) and stays there.

``check_vma=False`` is required because ``pallas_call`` has no varying-axes
rule; it is safe here since no out spec claims replication.

:func:`bg_denoise_sharded` and :func:`bg_temporal_sharded` are thin shims
that route their kwargs into a mesh-carrying :class:`repro.plan.BGPlan`, so
repeat dispatches hit the plan's per-(plan, mesh) compiled-executable cache
instead of this module maintaining its own shard_map/jit LRUs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bilateral_grid import BGConfig

__all__ = [
    "BATCH_AXIS",
    "batch_mesh",
    "pad_rows",
    "row_pad",
    "shard_batch_call",
    "bg_denoise_sharded",
    "bg_temporal_sharded",
]

BATCH_AXIS = "batch"


def batch_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """1-D data-parallel mesh over the first ``n_devices`` local devices."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if not 1 <= n <= len(devices):
        raise ValueError(f"n_devices={n} not in [1, {len(devices)}]")
    # Auto axes: the plan pads, shards and trims the batch axis itself, and
    # an Explicit axis (make_mesh's default) refuses the trim to a length
    # that is not a device multiple
    return jax.make_mesh(
        (n,), (BATCH_AXIS,), devices=devices[:n],
        axis_types=(jax.sharding.AxisType.Auto,),
    )


def _service_mesh(mesh: jax.sharding.Mesh | None) -> jax.sharding.Mesh | None:
    """Shared mesh default for the service entry points: auto-mesh over all
    local devices when more than one is present; ``None`` (and size-1
    meshes, normalized away by ``BGPlan``) degrade to the plain
    single-device call."""
    if mesh is None and jax.device_count() > 1:
        return batch_mesh()
    return mesh


def row_pad(nd: int, n: int) -> int:
    """Zero rows needed to bring a leading axis of ``n`` up to a device
    multiple (the shared ragged-batch rule: pad before shard_map, trim
    after)."""
    return -n % nd


def pad_rows(arr: jnp.ndarray, pad: int) -> jnp.ndarray:
    """``arr`` with ``pad`` zero rows appended to its leading axis."""
    return jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))


def shard_batch_call(fn, images: jnp.ndarray, mesh: jax.sharding.Mesh) -> jnp.ndarray:
    """Run per-frame-independent ``fn`` with the leading axis sharded on
    ``mesh``'s first axis.

    ``fn`` maps ``(b_shard, ...) -> (b_shard, ...)``; ragged batches are
    zero-padded to a device multiple here and trimmed from the result, so
    every shard traces with the same static shard shape.

    The shard_map wrapper is rebuilt per call (``fn`` is arbitrary); on a
    serving hot path prefer a mesh-carrying ``repro.plan.BGPlan``, whose
    compiled executable is cached per plan.
    """
    axis = mesh.axis_names[0]
    b = images.shape[0]
    padded = pad_rows(images, row_pad(int(mesh.devices.size), b))
    sharded = jax.shard_map(
        fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )
    return sharded(padded)[:b]


def bg_denoise_sharded(
    images: jnp.ndarray,
    cfg: BGConfig | None = None,
    mesh: jax.sharding.Mesh | None = None,
    *,
    interpret: bool | None = None,
    batch_tile: int | None = None,
    stream_input: bool = False,
    quantize_output: bool = False,
    plan=None,
) -> jnp.ndarray:
    """Data-parallel fused BG denoise: the multi-device service entry point.

    (b, h, w) or (h, w) -> float32, bit-identical to
    ``bg_fused_kernel_call(images, cfg, ...)`` for every batch/mesh shape.
    ``mesh=None`` builds a 1-D mesh over all local devices; with one device
    (or a size-1 mesh) this degrades to the plain single-device call — no
    shard_map, no padding, zero overhead. Batches smaller than the mesh are
    padded (idle devices denoise zero frames that are dropped).

    ``quantize_output=True`` additionally applies the paper's output rounding
    (elementwise, so it commutes with the sharding). Preferred form: a
    mesh-carrying ``repro.plan.BGPlan`` via ``plan=``.
    """
    from repro.plan import BGPlan, warn_legacy_dispatch

    if plan is None:
        if cfg is None:
            raise TypeError("bg_denoise_sharded needs cfg= or plan=")
        warn_legacy_dispatch("bg_denoise_sharded")
        plan = BGPlan(
            cfg=cfg,
            backend="fused_streamed" if stream_input else "fused",
            batch_tile=batch_tile,
            mesh=_service_mesh(mesh),
            quantize_output=quantize_output,
            interpret=interpret,
        )
    return plan(images)


def bg_temporal_sharded(
    frames: jnp.ndarray,
    carry: jnp.ndarray,
    alpha: jnp.ndarray,
    cfg: BGConfig | None = None,
    mesh: jax.sharding.Mesh | None = None,
    *,
    interpret: bool | None = None,
    batch_tile: int | None = None,
    quantize_output: bool = False,
    plan=None,
):
    """Data-parallel temporal fused BG denoise: the video warm-path entry.

    ``frames`` is the ``(n, h, w)`` one-frame-per-stream pack, ``carry`` the
    stacked ``(n, gx, gy, gz, 2)`` blurred-grid EMA states and ``alpha`` the
    length-n per-stream blend weights. Returns ``(out, new_carry)``: the
    stream axis shards exactly like the per-frame batch axis (carry/alpha
    rows travel with their stream's device), zero collectives cross the
    mesh, and ragged packs are padded with zero frames / zero carries / zero
    alphas that are dropped after. The *image output* is bit-identical to
    ``bg_fused_kernel_call(frames, cfg, carry=..., alpha=...)`` for every
    (n, device-count) pair; the carry agrees to <= 1 ulp when the per-shard
    dispatch geometry differs from the single-device tiling (LLVM FMA-lane
    selection in the in-kernel blend — see the bg_fused blend comment) and
    bit-exactly otherwise. ``mesh=None`` auto-meshes over all local devices;
    one device degrades to the plain call. Preferred form: a temporal
    ``repro.plan.BGPlan`` via ``plan=``.
    """
    from repro.plan import BGPlan, warn_legacy_dispatch

    if plan is None:
        if cfg is None:
            raise TypeError("bg_temporal_sharded needs cfg= or plan=")
        warn_legacy_dispatch("bg_temporal_sharded")
        plan = BGPlan(
            cfg=cfg,
            backend="fused",
            temporal=True,
            batch_tile=batch_tile,
            mesh=_service_mesh(mesh),
            quantize_output=quantize_output,
            interpret=interpret,
        )
    return plan(frames, carry=carry, alpha=alpha)
