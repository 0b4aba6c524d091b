"""The serving engine's own device work, as small named programs.

Around each plan dispatch the engine (and the video packer) stacks the
micro-batch's frames into one array and hands each request its own row of
the answer. Each of these is one jitted program whose operations run under a
``jax.named_scope``, so a profiler trace names them by stage instead of by
the primitive JAX happened to dispatch:

  ``bg.ingest`` -- :func:`stack_rows`: the per-frame widening to the plan's
                   dtype and the stack (frames, and in video the per-stream
                   carries)
  ``bg.split``  -- :func:`split_rows`: the per-request output rows
  ``bg.guard``  -- ``repro.reliability.finite_rows`` / ``carry_ok_rows``

:func:`ingest_rows` decides, from the dtypes it sees, how a micro-batch's
host frames reach :func:`stack_rows`. A host frame of a narrow dtype (an
integer of at most 2 bytes, float16, bfloat16 or float32) crosses the host
link as submitted and is widened on the device: an 8-bit frame moves 1 byte
per pixel instead of 4. Wider dtypes (float64, integers of 4 or more bytes)
and every host row of a batch whose host rows mix dtypes are converted to
float32 on the host first, so a mixed batch compiles no program that a
uniform one would not. Device arrays pass as they are. Every widening here
is exact, so both paths give the same bits.

The plan executable's own stages (``bg.relayout``, ``bg.kernel``,
``bg.trim``, ``bg.quantize``) are scoped in ``repro.plan`` and
``repro.kernels.bg_fused``.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ingest_rows", "split_rows", "stack_rows"]

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


@functools.partial(jax.jit, static_argnames="dtype")
def stack_rows(rows, dtype):
    """``(h, w)`` rows -> one ``(n, h, w)`` array of ``dtype`` (scope
    ``bg.ingest``). One program per row count, shape and dtype."""
    with jax.named_scope("bg.ingest"):
        return jnp.stack([r.astype(dtype) for r in rows])


@functools.partial(jax.jit, static_argnums=1)
def split_rows(x, n):
    """The first ``n`` rows of ``x`` as ``n`` arrays (scope ``bg.split``)."""
    with jax.named_scope("bg.split"):
        return tuple(x[i] for i in range(n))
