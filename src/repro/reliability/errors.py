"""Structured errors for the fault-tolerant serving layer.

Every failure a client can observe through an :class:`~concurrent.futures.
Future` resolves to one of these types (or a plain caller error like
``KeyError`` for a never-opened stream), so a service front can branch on
the *kind* of failure — shed vs timed-out vs corrupted — instead of parsing
message strings. Each exception carries its context as attributes; the
message is rendered from them.

The retry layer (``repro.reliability.retry``) treats ``KeyError`` /
``ValueError`` / ``TypeError`` as caller bugs and fails fast;
:class:`ReliabilityError` subclasses derive from ``RuntimeError`` so
transient faults (injected or real) stay retryable. The one exception is
:class:`AdmissionError`, which *is* a ``ValueError``: a rejected submit is
the caller's problem and must never burn retry budget.
"""
from __future__ import annotations

from typing import Hashable, Optional, Sequence

__all__ = [
    "ReliabilityError",
    "AdmissionError",
    "InjectedFault",
    "EngineTimeout",
    "DeadlineExceeded",
    "NonFiniteOutput",
    "AllBackendsFailed",
    "EngineClosed",
]


class ReliabilityError(RuntimeError):
    """Base class for structured serving failures (retryable by default)."""


class AdmissionError(ValueError):
    """A frame was rejected at submit time (shape / dtype / non-finite).

    A ``ValueError`` on purpose: admission failures are caller errors — the
    retry ladder fails them fast instead of burning attempts, and legacy
    callers catching ``ValueError`` keep working.
    """

    def __init__(self, reason: str, *, stream_id: Hashable = None):
        self.reason = reason
        self.stream_id = stream_id
        sid = "" if stream_id is None else f" (stream {stream_id!r})"
        super().__init__(f"frame rejected at admission{sid}: {reason}")


class InjectedFault(ReliabilityError):
    """A deterministic fault raised by ``repro.reliability.faults`` — the
    test double for a real device/dispatch error (retryable)."""

    def __init__(self, reason: str, *, dispatch: Optional[int] = None):
        self.reason = reason
        self.dispatch = dispatch
        super().__init__(reason)


class EngineTimeout(ReliabilityError):
    """The engine watchdog expired waiting for an in-flight batch.

    The device (or an injected hang) held ``block_until_ready`` past the
    per-batch deadline; the batch's futures fail with this error, the
    active backend's breaker records the failure, and the engine keeps
    serving.
    """

    def __init__(self, timeout_s: float, *, uids: Sequence[int] = ()):
        self.timeout_s = timeout_s
        self.uids = tuple(uids)
        super().__init__(
            f"in-flight batch exceeded the {timeout_s * 1e3:.0f}ms engine "
            f"watchdog (uids {list(self.uids)})"
        )


class DeadlineExceeded(ReliabilityError):
    """The request's latency deadline passed before dispatch; it was shed
    at collect time instead of being served at full cost past its SLA."""

    def __init__(self, uid: int, late_s: float):
        self.uid = uid
        self.late_s = late_s
        super().__init__(
            f"request {uid} shed: deadline passed {late_s * 1e3:.1f}ms "
            f"before dispatch"
        )


class NonFiniteOutput(ReliabilityError):
    """The post-dispatch finite-guard caught NaN/Inf in this request's
    output frame, or (``carry=True``) its stream's advanced temporal carry
    failed the carry guard, so the frame was computed from a poisoned
    state — the frame is withheld (a structured error beats silently
    serving corrupted pixels)."""

    def __init__(self, uid: int, *, stream_id: Hashable = None,
                 carry: bool = False):
        self.uid = uid
        self.stream_id = stream_id
        self.carry = carry
        sid = "" if stream_id is None else f" (stream {stream_id!r})"
        what = ("the stream's temporal carry failed its guard" if carry
                else "output frame contains non-finite values")
        super().__init__(f"request {uid}{sid}: {what}")


class AllBackendsFailed(ReliabilityError):
    """Every rung of the fallback ladder failed (or was circuit-open) for
    this dispatch. ``__cause__`` holds the last underlying failure."""

    def __init__(self, attempts: int, rungs: int):
        self.attempts = attempts
        self.rungs = rungs
        super().__init__(
            f"dispatch failed on all {rungs} fallback rung(s) "
            f"({attempts} attempt(s) total)"
        )


class EngineClosed(ReliabilityError):
    """The engine shut down before this request could be dispatched."""
