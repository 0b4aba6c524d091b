"""The serving engine's own device work, as small named programs.

Around each plan dispatch the engine (and the video packer) stacks the
micro-batch's frames into one array and hands each request its own row of
the answer. Each of these is one jitted program whose operations run under a
``jax.named_scope``, so a profiler trace names them by stage instead of by
the primitive JAX happened to dispatch:

  ``bg.ingest`` -- :func:`stack_rows`: the per-frame cast and the stack
                   (frames, and in video the per-stream carries)
  ``bg.split``  -- :func:`split_rows`: the per-request output rows
  ``bg.guard``  -- ``repro.reliability.finite_rows`` / ``carry_ok_rows``

The plan executable's own stages (``bg.relayout``, ``bg.kernel``,
``bg.trim``, ``bg.quantize``) are scoped in ``repro.plan`` and
``repro.kernels.bg_fused``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["host_f32", "split_rows", "stack_rows"]


def host_f32(frame):
    """A frame as float32 for :func:`stack_rows`: host arrays are converted
    on the host (as ``jnp.asarray(frame, float32)`` does), device arrays are
    passed as they are and cast on the device."""
    return frame if isinstance(frame, jax.Array) else np.asarray(frame, np.float32)


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
