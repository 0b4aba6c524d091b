"""Serving engines: LM continuous batching + the two frame-denoise fronts.

Frame serving comes in two flavors; pick by how the caller wants to wait:

  * ``frames.FrameDenoiseEngine`` — **synchronous** micro-batching. The
    caller's thread owns the loop (``submit``/``step``/``flush``); each
    dispatch stacks, launches, and returns request objects whose results the
    caller realizes. Simple, deterministic, no threads — right for batch
    jobs, tests, and single-tenant pipelines where the caller *is* the
    frame source.
  * ``async_engine.AsyncFrameEngine`` — **asynchronous** serving.
    ``submit`` returns a Future immediately; a background dispatch thread
    does deadline-aware micro-batching and double-buffered host->device
    feeding (stacking batch N+1 while batch N computes), and a completion
    thread resolves futures. Right for services: many producers, bounded
    queues for backpressure, latency budgets, multi-stream video via a
    ``repro.video`` packer, and strictly higher sustained frames/sec than
    the synchronous engine (gated in benchmarks/bench_video_stream.py).

The async front is additionally **fault-tolerant** (the
``repro.reliability`` wiring): admission validation at ``submit``
(``AdmissionError`` before a NaN frame can touch a queue or a temporal
carry), guarded dispatch with bounded retries and the backend fallback
ladder (``fused_streamed -> fused -> reference`` behind per-rung circuit
breakers), lazy per-row finite-guards on outputs and carries with
per-stream carry **quarantine**, collect-time shedding of past-deadline
requests (``DeadlineExceeded``), and a per-inflight-batch **watchdog**
(``EngineTimeout``) so a wedged device fails one batch, not the service.
Every failure a client observes through a Future is a typed
``repro.reliability.errors`` exception; ``EngineStats`` counts ``failed`` /
``retries`` / ``fallbacks`` / ``carry_resets`` / ``shed`` /
``watchdog_trips``; ``engine.fault_injector`` accepts a deterministic
``reliability.FaultInjector`` so every failure mode is drivable in tests
and the ``benchmarks/bench_bg_chaos.py`` CI soak. The synchronous engine
stays guard-free on purpose — it is the simple, deterministic oracle the
async front is equivalence-tested against.

**Telemetry** of the async front: ``EngineStats`` latencies run from
``submit`` to the future being set (the consumer's copy of the answer to
host memory comes after). Every request is stamped as it is taken off the
queue, as its batch closes, as the launch returns and as its future is set,
with the ``dispatch`` id of its batch; ``engine.timeline()`` returns the
last finished requests' stamps (``StageRecord``), from which queue, hold,
prep and completion time add up to each frame's latency. Under a profiler
the dispatch thread runs the spans ``engine.wait`` / ``engine.hold`` /
``engine.prep`` / ``engine.backpressure``, the completion thread
``engine.await`` / ``engine.resolve``, one per dispatch with its id and
size; the device programs are named ``jit_bg_<backend>`` with the scopes
``bg.relayout`` / ``bg.kernel`` / ``bg.trim`` / ``bg.quantize``, and the
engine's own ``bg.ingest`` / ``bg.guard`` / ``bg.split``.

**Scaling out**: one ``AsyncFrameEngine`` is a single worker. The fleet
layer (``repro.fleet``) fronts N of them behind a ``FleetRouter`` — sticky
per-stream affinity (a temporal carry lives on exactly one worker),
fleet-level admission at the router so workers run with
``admission_checks=False``, bounded per-worker backpressure that sheds at
the router before any engine queue can overflow, one controller-distributed
``BGPlan`` per fleet (mixed recipes refused at construction), and
drain-and-quarantine failover when a worker dies. ``EngineStats.merge``
rolls per-worker snapshots into exact fleet percentiles (union of the
latency reservoirs, never averaged percentiles); see the ``repro.fleet``
package docstring for the full architecture.
"""
from .async_engine import AsyncFrameEngine, AsyncFrameRequest, EngineStats, StageRecord
from .engine import Request, ServeEngine, make_prefill, make_serve_step
from .frames import FrameDenoiseEngine, FrameRequest
from .sampling import greedy, sample_temperature, sample_topk
