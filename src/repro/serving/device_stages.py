"""The serving engine's own device work, as small named programs.

Around each plan dispatch the engine (and the video packer) stacks the
micro-batch's frames into one array and hands each request its own row of
the answer. Each of these is one jitted program whose operations run under a
``jax.named_scope``, so a profiler trace names them by stage instead of by
the primitive JAX happened to dispatch:

  ``bg.ingest`` -- :func:`stack_rows`: the stack of the frames in their
                   common dtype (:func:`row_dtype`), and in video of the
                   per-stream carries in the plan's storage dtype
  ``bg.split``  -- :func:`split_rows`: the per-request output rows, each
                   in its answer dtype (:func:`answer_dtypes`)
  ``bg.guard``  -- ``repro.reliability.finite_rows`` / ``carry_ok_rows``

:func:`ingest_rows` decides, from the dtypes it sees, how a micro-batch's
host frames reach :func:`stack_rows`. A host frame of a narrow dtype (an
integer of at most 2 bytes, float16, bfloat16 or float32) crosses the host
link as submitted and is stacked in its own dtype: an 8-bit frame moves 1
byte per pixel over the link and into the plan, which widens it (and runs
grid creation in one bf16 pass for 8-bit levels, ``repro.kernels.bg_fused``).
Wider dtypes (float64, integers of 4 or more bytes) and every host row of a
batch whose host rows mix dtypes are converted to float32 on the host first,
so a mixed batch compiles no program that a uniform one would not. Device
arrays pass as they are. Every widening is exact, so both paths give the
same bits.

A mesh plan's micro-batch takes another road. :func:`place_rows` gives
each shard of the batch its rows and puts them straight on the shard's
chip, one thread per chip, so the four host links work at once; there one
:func:`stack_rows` stacks them in their own dtype and appends the zero rows
that pad the batch to a multiple of the mesh size (``bg_shard.row_pad``).
Device rows move chip to chip, never through the host. The shards form one
batch sharded ``P("batch")``, so no row passes through a chip that does not
own it, and the plan executable widens on each chip.
:func:`split_shards` then splits each shard's answer rows off on the chip
that made them (one ``split_rows`` per shard); pad rows are never split
off.

:func:`answer_dtypes` decides each answer's dtype from its own request's
frame, never from its batch-mates': a frame of 8-bit integer levels gets a
``uint8`` answer when the plan rounds its answer to whole levels that a
``uint8`` holds, so the client copies 1 byte per pixel, not 4; every other
frame gets the plan's float32 answer. The cast is exact (the plan has
already rounded and clipped), and it comes after the output guard, which
reads the float32 batch (``repro.reliability.finite_rows``), so a NaN is
flagged before a cast could turn it into a level.

The plan executable's own stages (``bg.relayout``, ``bg.kernel``,
``bg.trim``, ``bg.quantize``) are scoped in ``repro.plan`` and
``repro.kernels.bg_fused``.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.kernels.bg_fused import is_level_dtype
from repro.sharding.bg_shard import row_pad

__all__ = [
    "answer_dtypes", "ingest_rows", "place_rows", "row_dtype", "split_rows",
    "split_shards", "stack_rows",
]

# host dtypes that travel to the device as submitted (besides integers of at
# most 2 bytes); the rest are wider than the float32 they become
_NARROW_FLOATS = frozenset(np.dtype(d) for d in (np.float16, jnp.bfloat16, np.float32))


def _travels(dtype: np.dtype) -> bool:
    return dtype.isnative and (
        (dtype.kind in "iu" and dtype.itemsize <= 2) or dtype in _NARROW_FLOATS)


def ingest_rows(frames) -> Tuple[List, int]:
    """One micro-batch's frames as :func:`stack_rows` takes them, and how
    many host frames took the host path (converted to float32 on the host,
    as ``jnp.asarray(frame, float32)`` does).

    Host frames travel as submitted when they all share one dtype and it is
    narrow (module docstring); otherwise every host frame is converted.
    Device arrays are passed as they are and cast on the device."""
    rows = [f if isinstance(f, jax.Array) else np.asarray(f) for f in frames]
    host = [i for i, r in enumerate(rows) if not isinstance(r, jax.Array)]
    dtypes = {rows[i].dtype for i in host}
    if len(dtypes) <= 1 and all(_travels(d) for d in dtypes):
        return rows, 0
    for i in host:
        rows[i] = np.asarray(rows[i], np.float32)
    return rows, len(host)


def row_dtype(rows):
    """The dtype the rows of one micro-batch (from :func:`ingest_rows`) are
    stacked in: their common dtype, which is their own unless device rows
    of another dtype join them."""
    return jnp.result_type(*(r.dtype for r in rows))


@functools.partial(jax.jit, static_argnames=("dtype", "pad"))
def stack_rows(rows, dtype, pad=0):
    """``(h, w)`` rows -> one ``(n + pad, h, w)`` array of ``dtype``, the
    last ``pad`` rows zero (scope ``bg.ingest``). One program per row count,
    pad, shape and dtype."""
    with jax.named_scope("bg.ingest"):
        rows = [r.astype(dtype) for r in rows]
        return jnp.stack(rows + [jnp.zeros_like(rows[0])] * pad)


def answer_dtypes(plan, frames) -> Optional[Tuple]:
    """The dtype of each frame's answer from ``plan``, as :func:`split_rows`
    takes it: ``uint8`` for a frame of 8-bit integer levels
    (``repro.kernels.bg_fused.is_level_dtype``) when the plan quantizes to
    a whole ``intensity_max`` of at most 255, so that every answer value is
    a level a ``uint8`` holds; None (the plan's float32) for any other
    frame. Returns None when no answer is cast."""
    top = plan.cfg.intensity_max
    if not (plan.quantize_output and float(top).is_integer() and 0 <= top <= 255):
        return None
    dtypes = tuple(
        np.dtype(np.uint8)
        if is_level_dtype(f.dtype if hasattr(f, "dtype") else np.asarray(f).dtype)
        else None
        for f in frames)
    return dtypes if any(d is not None for d in dtypes) else None


@functools.partial(jax.jit, static_argnums=(1, 2))
def split_rows(x, n, dtypes=None):
    """The first ``n`` rows of ``x`` as ``n`` arrays (scope ``bg.split``),
    row ``i`` cast to ``dtypes[i]`` where ``dtypes`` (from
    :func:`answer_dtypes`) gives one."""
    with jax.named_scope("bg.split"):
        rows = tuple(x[i] for i in range(n))
        if dtypes is None:
            return rows
        return tuple(r if d is None else r.astype(d) for r, d in zip(rows, dtypes))


@functools.lru_cache(maxsize=None)
def _placers(n: int) -> ThreadPoolExecutor:
    """``n`` threads that put shards on their chips side by side (a
    transfer releases the GIL)."""
    return ThreadPoolExecutor(n, thread_name_prefix="bg-place")


def place_rows(rows, mesh) -> Tuple[jax.Array, int]:
    """One micro-batch's rows (from :func:`ingest_rows`) as one batch on
    ``mesh``, sharded over its batch axis, and the number of zero rows that
    pad it to a multiple of the mesh size (on the last shards). Each shard's
    rows go to its own device, host rows over its host link and device rows
    chip to chip, all shards at once; there :func:`stack_rows` stacks them
    in the rows' common dtype."""
    nd = int(mesh.devices.size)
    pad = row_pad(nd, len(rows))
    per = (len(rows) + pad) // nd
    dtype = row_dtype(rows)
    shape = (per * nd,) + tuple(rows[0].shape)
    sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    starts = {d: i[0].start or 0
              for d, i in sharding.addressable_devices_indices_map(shape).items()}

    def shard(device):
        mine = rows[starts[device]:starts[device] + per]
        if not mine:
            return jnp.zeros((per,) + shape[1:], dtype, device=device)
        return stack_rows(jax.device_put(mine, device), dtype, pad=per - len(mine))

    shards = list(_placers(nd).map(shard, starts))
    return jax.make_array_from_single_device_arrays(shape, sharding, shards), pad


def split_shards(x, n, dtypes=None) -> List:
    """The first ``n`` rows of the batch-sharded ``x`` as ``n`` arrays, each
    on the device of the shard that holds it and in its dtype from
    ``dtypes`` (:func:`split_rows`): one :func:`split_rows` per shard, on
    that shard's own data, so nothing crosses between chips."""
    rows = []
    for shard in sorted(x.addressable_shards, key=lambda s: s.index[0].start or 0):
        start = shard.index[0].start or 0
        k = min(n - start, shard.data.shape[0])
        if k > 0:
            mine = None if dtypes is None else dtypes[start:start + k]
            rows.extend(split_rows(shard.data, k, mine))
    return rows
