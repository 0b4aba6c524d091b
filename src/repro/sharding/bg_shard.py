"""Batch-axis device sharding for the fused bilateral-grid service path.

Frames are independent, so the TPU analogue of the paper's "add more
pipeline stages" is pure data parallelism: a 1-D ``batch`` mesh where each
device runs the whole fused GC||GF||TI macro-pipeline on its slice of the
frame batch. The same holds for the *temporal* video path: the per-stream
grid carry and alpha rows shard with their stream's frame, so each device
advances its streams' EMAs locally. Nothing in the kernel reads across
frames. A mesh-carrying :class:`repro.plan.BGPlan` dispatches over the mesh
this module builds; the contract:

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

The plan's ``shard_map`` runs with ``check_vma=False`` because
``pallas_call`` has no varying-axes rule; it is safe since no out spec
claims replication.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["BATCH_AXIS", "batch_mesh", "pad_rows", "row_pad"]

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


def row_pad(nd: int, n: int) -> int:
    """Zero rows needed to bring a leading axis of ``n`` up to a device
    multiple (the shared ragged-batch rule: pad before shard_map, trim
    after)."""
    return -n % nd


def pad_rows(arr: jnp.ndarray, pad: int) -> jnp.ndarray:
    """``arr`` with ``pad`` zero rows appended to its leading axis."""
    return jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))
