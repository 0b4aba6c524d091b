"""Admission validation and post-dispatch finite-guards.

"Bilateral filters: what they can and cannot do" (PAPERS.md) is explicit
that degenerate inputs need handling, not trust — and the temporal EMA makes
the stakes concrete: one NaN pixel splatted into the grid blurs across its
neighborhood, the carry blend ``G_t = (1-a)B_t + a G_{t-1}`` then folds the
NaN into the stream's history, and *every* subsequent frame of that stream
slices against a poisoned grid. Two cheap layers stop that:

  * **Admission** (:func:`validate_frame`) — host-side shape/dtype/finite
    checks at ``submit``, before a frame can touch the queue. A bad frame
    costs its caller an :class:`~repro.reliability.errors.AdmissionError`
    and nobody else anything.
  * **Post-dispatch guards** (:func:`finite_rows` / :func:`carry_ok_rows`) —
    per-pack ``jnp.isfinite`` reductions computed *lazily at dispatch* (a
    few hundred flops on tensors already in VMEM, riding the same async
    dataflow) and realized with the outputs at completion. Output rows that
    fail resolve their futures with ``NonFiniteOutput``; carry rows that
    fail (non-finite or out-of-range) trigger per-stream **quarantine**:
    ``MultiStreamPacker.quarantine`` resets the carry to cold, the next pack
    re-warms the stream through the PR-3 effective-alpha-0 machinery, and
    the stream is clean again within one frame instead of poisoned forever.

:class:`DispatchGuard` is the record that travels with each in-flight batch
from dispatch to completion: the lazy flag arrays plus the stream-id order
needed to map flag rows back to requests.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Hashable, Optional, Tuple

import numpy as np

from .errors import AdmissionError

__all__ = [
    "DEFAULT_CARRY_LIMIT",
    "DispatchGuard",
    "validate_frame",
    "finite_rows",
    "carry_ok_rows",
]

# Out-of-range bound for temporal carries: the carry is the blurred
# homogeneous grid (count, sum); counts are bounded by pixels-per-cell and
# the EMA's 1/(1-a) effective window, sums by 255x that — a full-HD stream
# at a = 0.99 stays under ~5e9, so 1e12 flags only genuinely runaway values
# (an Inf that decayed into huge-but-finite garbage, a corrupted exponent).
DEFAULT_CARRY_LIMIT = 1e12


@dataclasses.dataclass
class DispatchGuard:
    """Per-batch guard state: lazy flag arrays dispatched with the batch.

    ``out_ok`` is a lazy ``(n,)`` bool vector (True = row finite), ordered by
    ``order`` (stream ids, video mode) or positionally (``order=None``).
    ``carry_ok`` covers the ``carry_sids`` streams whose temporal carry
    advanced this pack. ``None`` fields mean "nothing to check".
    """

    out_ok: Optional[object] = None
    order: Optional[Tuple[Hashable, ...]] = None
    carry_sids: Tuple[Hashable, ...] = ()
    carry_ok: Optional[object] = None


def validate_frame(frame, *, stream_id: Hashable = None) -> np.ndarray:
    """Admission check for one submitted frame: 2-D, numeric, finite.

    Returns the frame as a numpy array (the form the dispatch thread stacks
    anyway); raises :class:`AdmissionError` (a ``ValueError``) otherwise.
    Host-side numpy — no device work, no sync.
    """
    try:
        arr = np.asarray(frame)
    except Exception as exc:
        raise AdmissionError(
            f"not convertible to an array: {exc}", stream_id=stream_id
        ) from exc
    if arr.ndim != 2:
        raise AdmissionError(
            f"expected a 2-D (h, w) frame, got shape {arr.shape}",
            stream_id=stream_id,
        )
    if arr.size == 0:
        raise AdmissionError("empty frame", stream_id=stream_id)
    if not np.issubdtype(arr.dtype, np.number) or np.issubdtype(
        arr.dtype, np.complexfloating
    ):
        raise AdmissionError(
            f"expected a real numeric dtype, got {arr.dtype}",
            stream_id=stream_id,
        )
    if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
        raise AdmissionError(
            "frame contains non-finite values (NaN/Inf)", stream_id=stream_id
        )
    return arr


@functools.lru_cache(maxsize=None)
def _guard_programs():
    """Both guards as jitted programs whose operations run under the scope
    ``bg.guard`` (built on first use: this module imports without JAX)."""
    import jax
    import jax.numpy as jnp

    def finite_rows(x):
        with jax.named_scope("bg.guard"):
            return jnp.all(jnp.isfinite(x).reshape(x.shape[0], -1), axis=1)

    def carry_ok_rows(carry, limit):
        with jax.named_scope("bg.guard"):
            flat = carry.reshape(carry.shape[0], -1)
            return jnp.all(jnp.isfinite(flat) & (jnp.abs(flat) < limit), axis=1)

    return jax.jit(finite_rows), jax.jit(carry_ok_rows, static_argnames="limit")


def finite_rows(x):
    """Lazy per-row finite flags: ``(n, ...) -> (n,)`` bool, True = finite.

    A ``jnp.isfinite`` reduction launched with the dispatch — the cheap
    post-dispatch output guard. Realize it alongside the outputs.
    """
    return _guard_programs()[0](x)


def carry_ok_rows(carry, limit: float = DEFAULT_CARRY_LIMIT):
    """Lazy per-stream carry health flags: finite AND within ``limit``.

    The quarantine detector: a False row means that stream's temporal carry
    would poison every later frame and must be reset to cold.
    """
    return _guard_programs()[1](carry, limit=limit)
