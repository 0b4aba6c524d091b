"""Async frame-denoise engine: pipelined host->device feeding behind futures.

A synchronous serving loop stacks a batch, dispatches it, and realizes the
results before it can take more frames — so the device idles while the host
stacks/converts, and the host idles while the device computes. This engine
splits that loop across threads:

  client threads   -- submit(frame) -> Future          (bounded queue)
  dispatch thread  -- collect micro-batch, transfer it in its own dtype,
                      stack and widen on the device, launch
                      (JAX async dispatch)              -> in-flight queue
  completion thread-- block_until_ready, resolve futures, record latency

The in-flight queue holds at most ``max_inflight`` (default 2) launched
batches: while batch N computes on the device, the dispatch thread is
already stacking and transferring batch N+1 (double buffering), and the
completion thread is realizing batch N-1's results — the device never waits
on host-side stacking, and ``put`` on a full in-flight queue is the
backpressure that stops the host from racing arbitrarily far ahead of the
device. Submission backpressure is the bounded request queue itself:
``submit`` blocks (or raises ``queue.Full`` with ``block=False``) when
``max_queue`` requests are pending.

Micro-batching is deadline-aware: a batch dispatches when it is full, when
the batch window since its first frame expires, or when any queued request's
deadline is within ``deadline_margin_ms`` — low-traffic frames are not held
hostage to batch-full, and latency-budgeted requests jump the window. A
request whose deadline has *already passed* at collect time is **shed**: its
future fails with a structured ``DeadlineExceeded`` instead of the batch
paying full dispatch cost for a frame its client has given up on.

Video mode: constructed with a ``repro.video.session.MultiStreamPacker``,
requests carry a ``stream_id`` and each micro-batch takes at most one frame
per stream (the temporal recursion is strictly sequential within a stream);
same-stream repeats are deferred to the next batch. Every pack is a single
fused-kernel dispatch — the temporal grid EMA runs inside the kernel
(``bg_fused_kernel_call(carry=, alpha=)``), so warm and cold streams mix in
one micro-batch and the pack's stream axis shards over the local mesh. The
per-stream grid carries chain through JAX's async dataflow, so back-to-back
packs still overlap.

Fault tolerance (the ``repro.reliability`` wiring — PR 6):

  * **Admission** — ``submit`` validates shape/dtype/finiteness host-side
    (``AdmissionError``) so one NaN camera frame cannot enter the pipeline,
    let alone the temporal EMA. Disable with ``admission_checks=False``.
  * **Guarded dispatch** — every launch runs through a
    ``reliability.GuardedDispatch``: bounded exponential-backoff retries,
    then the plan's **fallback ladder** (``fused_streamed -> fused ->
    reference``) behind per-rung circuit breakers. A transient fault costs
    a retry; a dead kernel backend serves degraded (reference-oracle)
    output instead of an exception. Caller errors (unknown stream, bad
    shape) still fail fast with the original exception.
  * **Finite-guards + carry quarantine** — each dispatch launches lazy
    per-row ``isfinite`` reductions over outputs (and, in video mode, the
    advanced temporal carries). At completion, a non-finite output row
    fails exactly that request with ``NonFiniteOutput``; a bad carry row
    quarantines exactly that stream (``packer.quarantine``: reset to cold,
    re-warm through the standard first-frame path) instead of poisoning
    every later frame, and withholds the frame that advanced it
    (``NonFiniteOutput(carry=True)``), which may read finite.
  * **Watchdog** — ``watchdog_ms`` bounds ``block_until_ready`` per
    in-flight batch. A wedged device (or injected hang) fails that batch's
    futures with a structured ``EngineTimeout``, charges the breaker of the
    rung that dispatched it, and the engine keeps serving. Stateless
    (non-video) batches get one synchronous guarded redispatch first —
    a transient completion failure costs a retry, not the batch.
  * **Fault injection** — assign ``engine.fault_injector`` (a
    ``reliability.FaultInjector``) to fire a deterministic fault schedule
    at the hook points above; ``benchmarks/bench_bg_chaos.py`` gates
    recovery throughput and zero-silent-corruption on it in CI.

Telemetry: ``stats()`` returns a structured :class:`EngineStats` snapshot
(queue/in-flight depth, dispatch count, mean batch size, p50/p99 request
latency, deadline misses, plus the reliability counters ``failed`` /
``retries`` / ``fallbacks`` / ``carry_resets`` / ``shed`` /
``watchdog_trips``) consumed by ``benchmarks/bench_video_stream.py`` and
its ``BENCH_<ts>.json`` exporter; ``stats()["key"]`` indexing survives as a
legacy shim. A request's latency runs from ``submit`` to its future being
set: the consumer's copy of the answer to host memory comes after it.

Every request carries ``time.monotonic`` stamps of its stages
(:class:`AsyncFrameRequest`): ``t_submit``; ``t_taken``, when the dispatch
thread takes it off the queue (or the held list) into its batch;
``t_closed``, when that batch closes; ``t_launched``, when the guarded
launch returns with the executable and its guards enqueued; ``t_resolved``,
when its future is set. So per frame, queue = taken - submit, hold = closed
- taken, prep = launched - closed, complete = resolved - launched, and the
four add up to the latency. Each request also carries the ``dispatch`` id of
the batch that took it. Finished requests land in a bounded ring (the last
``TIMELINE_SIZE``); :meth:`AsyncFrameEngine.timeline` returns its
:class:`StageRecord` tuples, and ``stats()`` reads its percentiles and
``mean_batch`` from the ring's newest 4096. The stamps are always taken.

The engine's two threads also run ``jax.profiler.TraceAnnotation`` spans,
one per dispatch and stage (never one per frame), each with the ``dispatch``
id and the batch size ``n`` as arguments; they cost nothing unless a
profiler trace is being taken, and then share its clock with the device:

  dispatch thread   ``engine.wait`` (blocked on an empty queue),
                    ``engine.hold`` (the batch window), ``engine.prep``
                    (stack, transfer, executable and guard dispatch; in
                    video mode ``packer.pack`` inside it; for a mesh plan
                    ``engine.place`` inside it, with the batch's ``pad``
                    rows as a third argument: each shard's rows put on its
                    chip, one thread per chip, and stacked and padded
                    there),
                    ``engine.backpressure`` (blocked on a full in-flight
                    queue)
  completion thread ``engine.await`` (``block_until_ready`` under the
                    watchdog), ``engine.resolve`` (guard flags, futures)

The device programs are named too: the engine's ``bg.ingest``, ``bg.guard``
and ``bg.split`` (``repro.serving.device_stages``) around the plan
executable ``jit_bg_<backend>`` with its ``bg.relayout``, ``bg.kernel``,
``bg.trim`` and ``bg.quantize`` stages (``repro.plan``).

Dispatch is plan-driven: pass a ``repro.plan.BGPlan`` via ``plan=`` (build
it with ``repro.plan.plan_for``), or in video mode a packer whose plan
carries the video dispatch.

An answer's dtype follows its own request's frame
(``device_stages.answer_dtypes``): a frame of 8-bit integer levels (``uint8``
or ``int8``) gets a ``uint8`` answer, the plan's quantized levels cast in
the ``bg.split`` program after the output guard has read them as float32;
every other frame gets the plan's float32 answer. The values are the same
either way, and a batch that mixes the two hands each request its own.
``EngineStats.level_answers`` counts the ``uint8`` answers handed out.
"""
from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import (
    Deque, Dict, Hashable, List, NamedTuple, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.bg_fused import is_level_dtype
from repro.reliability import (
    DeadlineExceeded,
    DispatchGuard,
    EngineClosed,
    EngineTimeout,
    GuardedDispatch,
    NonFiniteOutput,
    RetryPolicy,
    finite_rows,
    validate_frame,
)
from repro.serving.device_stages import (
    answer_dtypes, ingest_rows, place_rows, row_dtype, split_rows, split_shards,
    stack_rows,
)

__all__ = ["AsyncFrameEngine", "AsyncFrameRequest", "EngineStats", "StageRecord"]

_SENTINEL = object()
TIMELINE_SIZE = 65536  # finished requests kept in the stage ring
_STATS_WINDOW = 4096  # newest finished requests that stats() reads


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """End-of-interval engine telemetry snapshot (ROADMAP's "structured
    metrics" item): counts are since engine start, depths are instantaneous,
    latencies are over the last 4096 finished requests, each from ``submit``
    to its future being set (the consumer's copy of the answer to host
    memory is not in it), and ``mean_batch`` is over the launched
    dispatches among them. Both are read from the engine's stage ring
    (:meth:`AsyncFrameEngine.timeline`).

    Reliability counters (PR 6): ``failed`` — requests resolved with an
    exception (dispatch/completion failures, finite-guard rejections);
    ``retries`` — guarded-dispatch re-attempts; ``fallbacks`` — dispatches
    served from a fallback-ladder rung below the primary backend;
    ``carry_resets`` — temporal carries quarantined back to cold; ``shed``
    — requests dropped at collect time because their deadline had already
    passed; ``watchdog_trips`` — in-flight batches that exceeded the
    completion watchdog.

    Fleet counters (PR 9): ``restores`` — temporal carries re-installed from
    a warm snapshot after failover (the opposite of ``carry_resets``);
    ``reconnects`` — transport connections re-established to a
    process-spanning worker. Both are zero for in-process engines.

    ``host_casts`` — frames the engine (or its packer) converted to float32
    on the host because their dtype, or the mix of dtypes in their batch,
    could not travel to the device as submitted
    (``repro.serving.device_stages.ingest_rows``); ``completed -
    host_casts`` frames crossed the host link in their own dtype.

    ``mesh_pad_rows`` — zero rows dispatched to fill the shards of a mesh
    plan's ragged micro-batches (``device_stages.place_rows``): device work
    that answers no request.

    ``level_dispatches`` — launches (engine or packer) whose batch reached
    the plan as 8-bit levels (``repro.kernels.bg_fused.is_level_dtype``),
    so that a fused plan ran grid creation in one bf16 MXU pass.

    ``level_answers`` — answers handed out as ``uint8`` levels, because
    their own frame came as 8-bit levels
    (``repro.serving.device_stages.answer_dtypes``); ``completed -
    level_answers`` answers were handed out as float32.

    ``stats["key"]`` indexing is kept as a legacy shim for the former dict
    form; prefer attribute access. ``as_dict()`` feeds exporters (the
    ``BENCH_<ts>.json`` snapshot rows in benchmarks/bench_video_stream.py).

    ``latency_samples`` carries the snapshot's sorted latency reservoir
    (milliseconds, same window the percentiles were computed from) so
    :meth:`merge` can aggregate fleets **exactly** — percentiles of the
    concatenated samples — instead of averaging per-engine percentiles,
    which understates the tail precisely when one engine is the outlier.
    It is process-local diagnostic state: ``as_dict()`` leaves it out of
    exporter rows.
    """

    submitted: int
    completed: int
    dispatches: int
    queue_depth: int
    inflight_depth: int
    deadline_misses: int
    mean_batch: float
    latency_ms_p50: float
    latency_ms_p99: float
    failed: int = 0
    retries: int = 0
    fallbacks: int = 0
    carry_resets: int = 0
    shed: int = 0
    watchdog_trips: int = 0
    restores: int = 0
    reconnects: int = 0
    host_casts: int = 0
    mesh_pad_rows: int = 0
    level_dispatches: int = 0
    level_answers: int = 0
    latency_samples: Tuple[float, ...] = ()

    def __getitem__(self, key: str):
        if key not in self.__dataclass_fields__:
            raise KeyError(key)
        return getattr(self, key)

    def as_dict(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        d.pop("latency_samples")
        return d

    @classmethod
    def merge(cls, parts: Sequence["EngineStats"]) -> "EngineStats":
        """Aggregate engine snapshots into one fleet-level snapshot.

        Counters and depths sum; ``mean_batch`` is dispatch-weighted; the
        percentiles are computed over the **union** of the parts' latency
        reservoirs (exact, the whole point of carrying the samples). Parts
        without samples (hand-built snapshots) fall back to a
        completed-weighted average of their percentile fields — labelled
        approximation, only ever used when there is nothing better.
        """
        parts = [p for p in parts if p is not None]
        if not parts:
            return cls(0, 0, 0, 0, 0, 0, 0.0, 0.0, 0.0)
        samples = sorted(s for p in parts for s in p.latency_samples)

        def _pct(q: float) -> float:
            if samples:
                return samples[min(int(q * len(samples)), len(samples) - 1)]
            field = "latency_ms_p50" if q == 0.50 else "latency_ms_p99"
            weights = [p.completed for p in parts]
            total = sum(weights) or len(parts)
            return sum(
                getattr(p, field) * (w if sum(weights) else 1)
                for p, w in zip(parts, weights)
            ) / total

        dispatches = sum(p.dispatches for p in parts)
        mean_batch = (
            sum(p.mean_batch * p.dispatches for p in parts) / dispatches
            if dispatches
            else 0.0
        )
        return cls(
            submitted=sum(p.submitted for p in parts),
            completed=sum(p.completed for p in parts),
            dispatches=dispatches,
            queue_depth=sum(p.queue_depth for p in parts),
            inflight_depth=sum(p.inflight_depth for p in parts),
            deadline_misses=sum(p.deadline_misses for p in parts),
            mean_batch=mean_batch,
            latency_ms_p50=_pct(0.50),
            latency_ms_p99=_pct(0.99),
            failed=sum(p.failed for p in parts),
            retries=sum(p.retries for p in parts),
            fallbacks=sum(p.fallbacks for p in parts),
            carry_resets=sum(p.carry_resets for p in parts),
            shed=sum(p.shed for p in parts),
            watchdog_trips=sum(p.watchdog_trips for p in parts),
            restores=sum(p.restores for p in parts),
            reconnects=sum(p.reconnects for p in parts),
            host_casts=sum(p.host_casts for p in parts),
            mesh_pad_rows=sum(p.mesh_pad_rows for p in parts),
            level_dispatches=sum(p.level_dispatches for p in parts),
            level_answers=sum(p.level_answers for p in parts),
            latency_samples=tuple(samples),
        )


@dataclasses.dataclass
class AsyncFrameRequest:
    """One queued frame. ``deadline`` and the ``t_*`` stage stamps are
    absolute ``time.monotonic`` seconds (see the module docstring; a stage
    the request never reached stays None); ``dispatch`` is the id of the
    batch that took it; ``stream_id`` is set only in video (packer) mode."""

    uid: int
    frame: jnp.ndarray
    future: Future
    t_submit: float
    deadline: Optional[float] = None
    stream_id: Optional[Hashable] = None
    t_taken: Optional[float] = None
    t_closed: Optional[float] = None
    t_launched: Optional[float] = None
    t_resolved: Optional[float] = None
    dispatch: Optional[int] = None


class StageRecord(NamedTuple):
    """One finished request in :meth:`AsyncFrameEngine.timeline`: its stage
    stamps (``time.monotonic`` seconds; None for a stage it never reached,
    e.g. a launch that failed), the ``dispatch`` id and size ``n`` of the
    batch that took it, and whether its future got a result (``ok``)."""

    uid: int
    stream_id: Optional[Hashable]
    dispatch: Optional[int]
    n: int
    t_submit: float
    t_taken: Optional[float]
    t_closed: Optional[float]
    t_launched: Optional[float]
    t_resolved: float
    ok: bool


class AsyncFrameEngine:
    """Background micro-batching denoise engine with per-request futures."""

    def __init__(
        self,
        plan=None,
        *,
        packer=None,
        max_batch: int = 32,
        max_queue: int = 256,
        batch_window_ms: float = 2.0,
        deadline_margin_ms: float = 1.0,
        max_inflight: int = 2,
        fault_injector=None,
        watchdog_ms: Optional[float] = None,
        retry_policy: Optional[RetryPolicy] = None,
        fallback: bool = True,
        admission_checks: bool = True,
        output_guard: bool = True,
        carry_limit: Optional[float] = None,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        if watchdog_ms is not None and watchdog_ms <= 0:
            raise ValueError(f"watchdog_ms must be > 0 or None, got {watchdog_ms}")
        if packer is not None:
            # video mode dispatches through the packer's own plan — a
            # second, different plan would be silently ignored
            if plan is not None and plan is not packer.plan:
                raise ValueError(
                    "pass either plan= or packer= (video mode dispatches "
                    "the packer's plan); got two different plans"
                )
            plan = packer.plan
        elif plan is None:
            raise TypeError(
                "AsyncFrameEngine needs plan= (build one with "
                "repro.plan.plan_for) or packer="
            )
        elif not plan.quantize_output:
            raise ValueError(
                "AsyncFrameEngine serves quantized frames; build the plan "
                "with quantize_output=True"
            )
        self.plan = plan
        self.max_batch = max_batch
        self.batch_window = batch_window_ms / 1e3
        self.deadline_margin = deadline_margin_ms / 1e3
        self.packer = packer

        # reliability wiring (repro.reliability; see the module docstring)
        self.fault_injector = fault_injector  # assignable at runtime
        self.watchdog = None if watchdog_ms is None else watchdog_ms / 1e3
        self.admission_checks = admission_checks
        self.output_guard = output_guard
        self.carry_limit = carry_limit
        ladder = self.plan.fallback_ladder() if fallback else (self.plan,)
        self._guard = GuardedDispatch(
            ladder,
            retry_policy,
            on_retry=self._count_retry,
            on_fallback=self._count_fallback,
        )
        self._packer_lock = threading.Lock()

        self._queue: "queue.Queue" = queue.Queue(maxsize=max_queue)
        self._inflight: "queue.Queue" = queue.Queue(maxsize=max_inflight)
        self._held: Deque[AsyncFrameRequest] = deque()  # deferred same-stream
        self._uid = itertools.count()
        self._closed = False
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._outstanding = 0
        self._drained = threading.Condition(self._lock)
        # telemetry
        self._ring: Deque[StageRecord] = deque(maxlen=TIMELINE_SIZE)
        self._next_dispatch = 0  # id of the next batch to close
        self._dispatches = 0
        self._completed = 0
        self._submitted = 0
        self._deadline_misses = 0
        self._failed = 0
        self._retries = 0
        self._fallbacks = 0
        self._carry_resets = 0
        self._shed = 0
        self._host_casts = 0
        self._mesh_pad_rows = 0
        self._level_dispatches = 0
        self._level_answers = 0
        self._watchdog_trips = 0

        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="bg-frame-dispatch", daemon=True
        )
        self._completer = threading.Thread(
            target=self._complete_loop, name="bg-frame-complete", daemon=True
        )
        self._dispatcher.start()
        self._completer.start()

    @property
    def cfg(self):
        """The grid/window configuration of the plan this engine serves."""
        return self.plan.cfg

    # ------------------------------------------------------------- clients
    def submit(
        self,
        frame,
        stream_id: Optional[Hashable] = None,
        deadline_ms: Optional[float] = None,
        block: bool = True,
        timeout: Optional[float] = None,
    ) -> Future:
        """Queue one frame; returns a Future resolving to the denoised frame:
        ``uint8`` levels for a frame of 8-bit integer levels, else float32
        (the module docstring).

        Blocks when ``max_queue`` requests are already pending (``block=False``
        raises ``queue.Full`` instead — the service's load-shed hook).
        ``deadline_ms`` is a latency budget from now; an expiring deadline
        forces its micro-batch out early, and a deadline that has already
        passed by collect time sheds the request with ``DeadlineExceeded``.
        Raises ``AdmissionError`` (a ``ValueError``) for malformed or
        non-finite frames — rejected here, before they can touch the queue
        or a temporal carry.
        """
        if self.packer is not None and stream_id is None:
            raise ValueError("video mode: submit needs a stream_id")
        if self.admission_checks:
            frame = validate_frame(frame, stream_id=stream_id)
        inj = self.fault_injector
        if inj is not None:
            # post-admission hook: simulates in-flight corruption that
            # admission cannot see (the quarantine machinery's test double)
            frame = inj.corrupt_frame(frame, stream_id)
        now = time.monotonic()
        req = AsyncFrameRequest(
            uid=next(self._uid),
            frame=frame,
            future=Future(),
            t_submit=now,
            deadline=None if deadline_ms is None else now + deadline_ms / 1e3,
            stream_id=stream_id,
        )
        with self._lock:
            # closed-check and outstanding-increment are atomic with close()'s
            # flag set: a submit can never slip its request in behind the
            # shutdown sentinel (close's flush waits on _outstanding first)
            if self._closed:
                raise EngineClosed("engine is closed")
            self._outstanding += 1
            self._submitted += 1
        try:
            self._queue.put(req, block=block, timeout=timeout)
        except queue.Full:
            with self._lock:
                self._outstanding -= 1
                self._submitted -= 1
            raise
        return req.future

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted frame has resolved. True on success."""
        end = None if timeout is None else time.monotonic() + timeout
        with self._drained:
            while self._outstanding:
                left = None if end is None else end - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._drained.wait(timeout=left)
        return True

    def close(self, timeout: float = 30.0) -> None:
        """Drain outstanding work, then stop both threads (best-effort within
        ``timeout`` — the threads are daemons, so a wedged device can delay
        but never hang interpreter exit).

        Robust to a timed-out flush: the ``_stop`` event (polled by the
        dispatch loop every 100ms and by its in-flight ``put``) guarantees
        shutdown makes progress even when the request queue is still full —
        the old path gave up on ``queue.Full`` and joined neither thread.
        Requests still queued at stop fail with structured ``EngineClosed``,
        so no future is ever left pending.
        """
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self.flush(timeout=timeout)
        self._stop.set()
        try:
            # best-effort wake-up; a full queue is fine — the dispatch
            # loop's 100ms poll notices _stop without it
            self._queue.put_nowait(_SENTINEL)
        except queue.Full:
            pass
        self._dispatcher.join(timeout=timeout)
        self._completer.join(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ----------------------------------------------------------- telemetry
    def timeline(self) -> List[StageRecord]:
        """The stage stamps of the finished requests still in the ring
        (oldest first): how an operator or a benchmark writes out where each
        frame's latency went. Requests shed at collect time are not in it
        (``stats().shed`` counts them)."""
        with self._lock:
            return list(self._ring)

    def stats(self) -> EngineStats:
        def _pct(lat, q):
            return lat[min(int(q * len(lat)), len(lat) - 1)] * 1e3 if lat else 0.0

        with self._lock:
            recent = list(itertools.islice(reversed(self._ring), _STATS_WINDOW))
            lat = sorted(r.t_resolved - r.t_submit for r in recent)
            sizes = list(
                {r.dispatch: r.n for r in recent if r.t_launched is not None}.values()
            )
            return EngineStats(
                submitted=self._submitted,
                completed=self._completed,
                dispatches=self._dispatches,
                queue_depth=self._queue.qsize(),
                inflight_depth=self._inflight.qsize(),
                deadline_misses=self._deadline_misses,
                mean_batch=(sum(sizes) / len(sizes)) if sizes else 0.0,
                latency_ms_p50=_pct(lat, 0.50),
                latency_ms_p99=_pct(lat, 0.99),
                failed=self._failed,
                retries=self._retries,
                fallbacks=self._fallbacks,
                carry_resets=self._carry_resets,
                shed=self._shed,
                watchdog_trips=self._watchdog_trips,
                restores=getattr(self.packer, "carry_restores", 0) or 0,
                host_casts=self._host_casts
                + (getattr(self.packer, "host_casts", 0) or 0),
                mesh_pad_rows=self._mesh_pad_rows,
                level_dispatches=self._level_dispatches
                + (getattr(self.packer, "level_dispatches", 0) or 0),
                level_answers=self._level_answers,
                latency_samples=tuple(x * 1e3 for x in lat),
            )

    def _count_retry(self) -> None:
        with self._lock:
            self._retries += 1

    def _count_fallback(self) -> None:
        with self._lock:
            self._fallbacks += 1

    # ------------------------------------------------------------ dispatch
    def _get_next(self, timeout: Optional[float]):
        """Next request: deferred same-stream holdovers first, then the queue."""
        if self._held:
            return self._held.popleft()
        try:
            return self._queue.get(timeout=timeout)
        except queue.Empty:
            return None

    def _shed_expired(self, req: AsyncFrameRequest) -> bool:
        """Load-shedding at collect time: a request whose deadline already
        passed fails with structured ``DeadlineExceeded`` instead of being
        dispatched at full cost past its SLA (ROADMAP item 1)."""
        if req.deadline is None:
            return False
        now = time.monotonic()
        if now <= req.deadline:
            return False
        if req.future.set_running_or_notify_cancel():
            req.future.set_exception(
                DeadlineExceeded(req.uid, late_s=now - req.deadline)
            )
        with self._lock:
            self._shed += 1
            self._deadline_misses += 1
            self._outstanding -= 1
            self._drained.notify_all()
        return True

    def _drain_on_stop(self) -> None:
        """Fail whatever is still queued/held at shutdown (a timed-out flush
        left stragglers) so no future is ever abandoned pending."""
        leftovers: List[AsyncFrameRequest] = list(self._held)
        self._held.clear()
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is _SENTINEL:
                continue
            leftovers.append(item)
        if leftovers:
            self._finish(
                leftovers, error=EngineClosed("engine closed before dispatch")
            )

    def _collect_batch(self) -> Optional[List[AsyncFrameRequest]]:
        """Block for the first request, then fill until batch-full, window
        expiry, or an imminent request deadline. Sheds already-expired
        requests. Returns None on shutdown. Stamps ``t_taken`` on each
        request as it is taken, and ``t_closed`` and ``dispatch`` on the
        batch it returns."""
        d = self._next_dispatch
        while True:
            with TraceAnnotation("engine.wait", dispatch=d, n=0):
                first = self._get_next(timeout=0.1)
            if first is None:
                if self._stop.is_set():
                    self._drain_on_stop()
                    return None
                return []
            if first is _SENTINEL:
                self._drain_on_stop()
                return None
            if self._shed_expired(first):
                continue
            first.t_taken = time.monotonic()
            break
        with TraceAnnotation("engine.hold", dispatch=d, n=0):
            batch = self._fill(first)
        t = time.monotonic()
        for req in batch:
            req.t_closed = t
            req.dispatch = d
        self._next_dispatch = d + 1
        return batch

    def _fill(self, first: AsyncFrameRequest) -> List[AsyncFrameRequest]:
        """``first``'s batch: take requests until batch-full, window expiry
        or an imminent deadline; same-stream repeats go to the held list."""
        batch = [first]
        streams = {first.stream_id}
        deferred: List[AsyncFrameRequest] = []
        target = self.max_batch
        if self.packer is not None:
            # one frame per stream per pack: a batch can never exceed the
            # live-stream count, so don't wait out the window for frames that
            # could only be same-stream repeats
            target = max(1, min(target, self.packer.live()))
        t_out = time.monotonic() + self.batch_window
        if first.deadline is not None:
            t_out = min(t_out, first.deadline - self.deadline_margin)
        while len(batch) < target:
            left = t_out - time.monotonic()
            if left <= 0:
                break
            nxt = self._get_next(timeout=left)
            if nxt is None:
                break
            if nxt is _SENTINEL:
                try:  # re-arm shutdown for the next loop
                    self._queue.put_nowait(_SENTINEL)
                except queue.Full:
                    self._stop.set()  # the 100ms poll path takes over
                break
            if self._shed_expired(nxt):
                continue
            if self.packer is not None and nxt.stream_id in streams:
                deferred.append(nxt)  # one frame per stream per pack
                continue
            nxt.t_taken = time.monotonic()
            batch.append(nxt)
            streams.add(nxt.stream_id)
            if nxt.deadline is not None:
                t_out = min(t_out, nxt.deadline - self.deadline_margin)
        self._held.extend(deferred)
        return batch

    def _launch_with(self, plan, batch: List[AsyncFrameRequest]):
        """Transfer, stack and dispatch (async) one micro-batch via
        ``plan``. Returns ``(outs, guard)``: the lazy per-request outputs in
        submission order, each in its own answer dtype, plus the batch's
        lazy finite-guard flags, which read the float32 batch. A mesh
        plan's batch is padded and placed across its chips in an
        ``engine.place`` span, and each answer stays on its shard's chip."""
        if self.packer is not None:
            by_sid = {r.stream_id: r.frame for r in batch}
            with self._packer_lock:
                out, guard = self.packer.pack_guarded(
                    by_sid, plan=None if plan is self.plan else plan,
                    carry_limit=self.carry_limit,
                )
            return [out[r.stream_id] for r in batch], guard
        frames = [r.frame for r in batch]
        dtypes = answer_dtypes(plan, frames)
        rows, casts = ingest_rows(frames)
        if casts:
            with self._lock:
                self._host_casts += casts
        n = len(batch)
        if plan.mesh is None:
            stacked = stack_rows(rows, row_dtype(rows))
            stacked = jax.device_put(stacked)  # overlap transfer with compute
            out = plan(stacked)
            self._count_levels(stacked)
            guard = DispatchGuard(
                out_ok=finite_rows(out) if self.output_guard else None
            )
            return list(split_rows(out, n, dtypes)), guard
        with TraceAnnotation("engine.place", dispatch=batch[0].dispatch, n=n) as span:
            placed, pad = place_rows(rows, plan.mesh)
            span.set_metadata(pad=pad)
        if pad:
            with self._lock:
                self._mesh_pad_rows += pad
        out = plan(placed)
        self._count_levels(placed)
        guard = DispatchGuard(out_ok=finite_rows(out) if self.output_guard else None)
        return split_shards(out, n, dtypes), guard

    def _count_levels(self, batch) -> None:
        if is_level_dtype(batch.dtype):
            with self._lock:
                self._level_dispatches += 1

    def _guarded_launch(self, batch: List[AsyncFrameRequest]):
        """One guarded dispatch: retries + fallback ladder + breakers.
        Returns ``(outs, guard, rung, dispatch_index)``."""
        box = {}

        def attempt(plan):
            inj = self.fault_injector
            box["didx"] = inj.on_dispatch(plan.backend) if inj else None
            return self._launch_with(plan, batch)

        (outs, guard), rung = self._guard.call(attempt)
        return outs, guard, rung, box.get("didx")

    def _dispatch_loop(self):
        while True:
            batch = self._collect_batch()
            if batch is None:  # shutdown: propagate downstream
                try:
                    self._inflight.put(_SENTINEL, timeout=1.0)
                except queue.Full:
                    pass  # completer wedged; it is a daemon
                return
            if not batch:
                continue
            d, n = batch[0].dispatch, len(batch)
            try:
                with TraceAnnotation("engine.prep", dispatch=d, n=n):
                    outs, guard, rung, didx = self._guarded_launch(batch)
            except Exception as exc:  # caller errors + exhausted ladder
                self._finish(batch, error=exc)
                continue
            t = time.monotonic()
            for req in batch:
                req.t_launched = t
            with self._lock:
                self._dispatches += 1
            # backpressure: at most max_inflight launched batches downstream;
            # stop-aware so a wedged completion thread cannot pin shutdown
            item = (batch, outs, guard, rung, didx)
            with TraceAnnotation("engine.backpressure", dispatch=d, n=n):
                while True:
                    try:
                        self._inflight.put(item, timeout=0.2)
                        break
                    except queue.Full:
                        if self._stop.is_set():
                            self._finish(
                                batch,
                                error=EngineClosed("engine closed mid-flight"),
                            )
                            break

    # ---------------------------------------------------------- completion
    def _await(self, payload, didx, batch, with_hook: bool = True):
        """Watchdog-bounded realization of ``payload`` (any pytree).

        Runs the injected completion hook (simulated hangs) plus
        ``block_until_ready`` inside the monitored region; a wedged device
        and an injected hang are indistinguishable past the deadline —
        both raise structured ``EngineTimeout`` and count a watchdog trip.
        """

        def work():
            inj = self.fault_injector if with_hook else None
            if inj is not None:
                inj.on_complete(didx)
            return jax.block_until_ready(payload)

        if self.watchdog is None:
            return work()
        box = {}

        def runner():
            try:
                box["ok"] = work()
            except BaseException as exc:  # surfaced on the waiting side
                box["err"] = exc

        t = threading.Thread(target=runner, name="bg-frame-await", daemon=True)
        t.start()
        t.join(self.watchdog)
        if t.is_alive():
            with self._lock:
                self._watchdog_trips += 1
            raise EngineTimeout(self.watchdog, uids=[r.uid for r in batch])
        if "err" in box:
            raise box["err"]
        return box["ok"]

    def _quarantine(self, sids) -> None:
        """Reset the given streams' temporal carries to cold (per-stream
        quarantine), counting actual resets."""
        if self.packer is None or not sids:
            return
        n = 0
        with self._packer_lock:
            for sid in sids:
                if self.packer.quarantine(sid):
                    n += 1
        if n:
            with self._lock:
                self._carry_resets += n

    def _resolve(self, batch, outs, guard, out_ok, carry_ok, didx=None) -> None:
        """Post-completion guard pass + future resolution for one batch."""
        # carry quarantine: exactly the streams whose carry went bad
        bad_carry = []
        if carry_ok is not None and len(guard.carry_sids):
            flags = np.asarray(carry_ok)
            bad_carry = [s for s, ok in zip(guard.carry_sids, flags) if not ok]
            self._quarantine(bad_carry)
        errors = None
        if self.output_guard and (out_ok is not None or bad_carry):
            flags = None if out_ok is None else np.asarray(out_ok)
            pos = (
                {sid: i for i, sid in enumerate(guard.order)}
                if guard.order is not None
                else None
            )
            errors = []
            for j, req in enumerate(batch):
                row = j if pos is None else pos[req.stream_id]
                if flags is not None and not bool(flags[row]):
                    errors.append(NonFiniteOutput(req.uid, stream_id=req.stream_id))
                elif req.stream_id in bad_carry:
                    # a poisoned carry can blend to a finite frame (the
                    # kernel's normalization maps a NaN weight to 0), which
                    # the output guard cannot see: withhold it too
                    errors.append(NonFiniteOutput(
                        req.uid, stream_id=req.stream_id, carry=True))
                else:
                    errors.append(None)
            if not any(e is not None for e in errors):
                errors = None
        self._finish(batch, outs=outs, errors=errors)
        # injected carry corruption/loss lands after a healthy completion —
        # the poison the *next* pack's guard flags must catch
        inj = self.fault_injector
        if inj is not None and self.packer is not None and didx is not None:
            with self._packer_lock:
                inj.apply_carry_faults(self.packer.sessions, didx)

    def _on_completion_failure(self, batch, guard, rung, exc) -> None:
        """A launched batch failed to realize (device error, watchdog trip).

        Charges the dispatching rung's breaker. Video packs are stateful —
        their futures fail structurally and their streams' carries are
        quarantined *precisely*: a short hookless re-await of the lazy
        carry-health flags distinguishes "computation fine, completion was
        held up" (reset only genuinely bad rows — zero for a pure hang)
        from "carries never realized" (reset every stream in the pack; the
        safe default for a truly wedged device). Stateless batches instead
        get one synchronous guarded redispatch — retry + ladder + watchdog
        — so a transient completion failure still serves results.
        """
        self._guard.record_remote_failure(rung)
        if self.packer is not None:
            suspects = list(guard.carry_sids)
            if suspects and guard.carry_ok is not None:
                try:
                    flags = np.asarray(
                        self._await(guard.carry_ok, None, batch, with_hook=False)
                    )
                    suspects = [
                        s for s, ok in zip(guard.carry_sids, flags) if not ok
                    ]
                except Exception:
                    pass  # flags unrealizable -> reset the whole pack
            self._quarantine(suspects)
            self._finish(batch, error=exc)
            return
        try:

            def attempt(plan):
                inj = self.fault_injector
                didx = inj.on_dispatch(plan.backend) if inj else None
                outs, guard2 = self._launch_with(plan, batch)
                ready = self._await((outs, guard2.out_ok), didx, batch)
                return ready[0], guard2, ready[1]

            (outs, guard2, out_ok), _rung = self._guard.call(attempt)
        except Exception as exc2:
            self._finish(batch, error=exc2)
            return
        self._resolve(batch, outs, guard2, out_ok, None)

    def _finish(self, batch, outs=None, error=None, errors=None):
        now = time.monotonic()
        # Resolve futures BEFORE announcing completion: flush() returning must
        # imply every future is done. A client-cancelled future is skipped
        # (set_running_or_notify_cancel returns False and a RUNNING future can
        # no longer be cancelled, so the set below cannot race).
        per_req = errors if errors is not None else [error] * len(batch)
        for i, req in enumerate(batch):
            if not req.future.set_running_or_notify_cancel():
                continue
            if per_req[i] is not None:
                req.future.set_exception(per_req[i])
            else:
                req.future.set_result(outs[i])
        with self._lock:
            for i, req in enumerate(batch):
                req.t_resolved = now
                self._ring.append(StageRecord(
                    req.uid, req.stream_id, req.dispatch, len(batch),
                    req.t_submit, req.t_taken, req.t_closed, req.t_launched,
                    now, per_req[i] is None,
                ))
                if req.deadline is not None and now > req.deadline:
                    self._deadline_misses += 1
                self._completed += per_req[i] is None
                self._level_answers += (
                    per_req[i] is None and outs[i].dtype == np.uint8)
                self._failed += per_req[i] is not None
            self._outstanding -= len(batch)
            self._drained.notify_all()

    def _complete_loop(self):
        while True:
            try:
                item = self._inflight.get(timeout=0.2)
            except queue.Empty:
                # stop-aware fallback: when shutdown raced the sentinel out
                # of the in-flight queue (a full queue at close), exit once
                # the dispatcher is gone and nothing is left to realize
                if self._stop.is_set() and not self._dispatcher.is_alive():
                    return
                continue
            if item is _SENTINEL:
                return
            batch, outs, guard, rung, didx = item
            d, n = batch[0].dispatch, len(batch)
            try:
                with TraceAnnotation("engine.await", dispatch=d, n=n):
                    outs, out_ok, carry_ok = self._await(
                        (outs, guard.out_ok, guard.carry_ok), didx, batch
                    )
            except Exception as exc:
                with TraceAnnotation("engine.resolve", dispatch=d, n=n):
                    self._on_completion_failure(batch, guard, rung, exc)
                continue
            with TraceAnnotation("engine.resolve", dispatch=d, n=n):
                self._resolve(batch, outs, guard, out_ok, carry_ok, didx=didx)
