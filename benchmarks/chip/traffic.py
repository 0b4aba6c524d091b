"""The load generator: arrival schedules made from a seed, and the client
loops that drive the engine with them.

A traffic mix is a JSON file under ``traffic/`` read by these two loops:

``"loop": "closed"`` -- ``outstanding`` frames in flight; each frame that
  comes back to host memory is answered by the next submission, until the
  window closes. Frames cycle through a pool of ``pool_frames`` distinct
  frames.

``"loop": "open"`` -- ``streams`` cameras, stream ``s`` at ``fps`` (a number,
  or one per stream) frames per second. Each stream's frames fall due on its
  own clock: ``phase_s + k / fps``. The mix lists ``phases``, one per
  stream as a fraction of its frame period: one arrangement of
  unsynchronized cameras. The run's seed permutes which stream takes which
  phase and shifts them all by one offset, so every seed offers the same
  arrivals in another order. Each stream is a window panning over its own
  scene, ``pool_frames`` long, played forward and back.

Either loop stamps when each frame was due (open) or sent (closed) and when
its denoised frame reached host memory in the client. Answers are brought
to host memory by a pool of ``FETCH_THREADS`` client threads; the closed
loop submits from its own thread, never from a fetch thread. The client's
submit and fetch calls are ``TraceAnnotation`` spans (``client.submit``,
``client.fetch``), so a profiler trace can say what the host did in a gap.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

DRAIN_S = 60.0  # how long past the close the client waits for answers
FETCH_THREADS = 4  # client threads that bring answers to host memory


@dataclass
class Arrival:
    due: float          # seconds after the window opens
    stream: int
    index: int          # position in the stream's sequence


@dataclass
class Outcome:
    """What the client saw of one frame."""

    stream: int
    index: int
    due: float                      # window-relative seconds
    sent: float = float("nan")      # window-relative seconds
    done: float = float("inf")      # window-relative; inf = never came
    error: str | None = None
    output: np.ndarray | None = field(default=None, repr=False)


def stream_rates(traffic: dict) -> list:
    fps = traffic["fps"]
    n = traffic["streams"]
    return [float(f) for f in (fps if isinstance(fps, list) else [fps] * n)]


def phases(traffic: dict, seed: int) -> list:
    """One phase per stream, in seconds within its frame period."""
    rates = stream_rates(traffic)
    base = traffic["phases"]
    if len(base) != len(rates):
        raise ValueError(f"the mix lists {len(base)} phases for {len(rates)} streams")
    rng = np.random.default_rng([int(seed), 2])
    order = rng.permutation(len(rates))
    shift = rng.random()
    return [float(((base[order[s]] + shift) % 1.0) / rates[s]) for s in range(len(rates))]


def open_schedule(traffic: dict, seed: int, seconds: float) -> list:
    """Every frame due in ``[0, seconds)``, in order of due time."""
    out = []
    for s, (rate, ph) in enumerate(zip(stream_rates(traffic), phases(traffic, seed))):
        k = 0
        while ph + k / rate < seconds:
            out.append(Arrival(ph + k / rate, s, k))
            k += 1
    out.sort(key=lambda a: (a.due, a.stream))
    return out


class Fetcher:
    """A pool of client threads that bring each answer to host memory as
    soon as its future resolves. ``spans`` holds each fetch's window-relative
    ``(start, end)``, from which the pool's busy share is read."""

    def __init__(self, t0: float, keep, threads: int, on_done=None):
        self.t0 = t0
        self.keep = keep          # keep(outcome) -> bool: hold the output
        self.on_done = on_done    # called in a fetch thread after each answer
        self.done_q: "queue.Queue" = queue.Queue()
        self.pending = 0
        self.spans = []
        self.lock = threading.Lock()
        self.idle = threading.Condition(self.lock)
        self.threads = [threading.Thread(target=self._loop, name=f"client-fetch-{i}",
                                         daemon=True) for i in range(int(threads))]
        for t in self.threads:
            t.start()

    def watch(self, fut, outcome: Outcome) -> None:
        with self.lock:
            self.pending += 1
        fut.add_done_callback(lambda f: self.done_q.put((f, outcome)))

    def _loop(self) -> None:
        import jax

        while True:
            item = self.done_q.get()
            if item is None:
                return
            fut, oc = item
            start = time.perf_counter() - self.t0
            try:
                with jax.profiler.TraceAnnotation("client.fetch"):
                    oc.output = np.asarray(fut.result())
                oc.done = time.perf_counter() - self.t0
                if not self.keep(oc):
                    oc.output = None
            except Exception as exc:  # a failed frame is an outcome, not a crash
                oc.error = f"{type(exc).__name__}: {exc}"
            end = time.perf_counter() - self.t0
            if self.on_done is not None:
                self.on_done(oc)
            with self.lock:
                self.spans.append((start, end))
                self.pending -= 1
                self.idle.notify_all()

    def drain(self, timeout: float) -> bool:
        end = time.monotonic() + timeout
        with self.lock:
            while self.pending:
                left = end - time.monotonic()
                if left <= 0:
                    return False
                self.idle.wait(left)
        return True

    def close(self) -> None:
        for _ in self.threads:
            self.done_q.put(None)
        for t in self.threads:
            t.join(timeout=10)

    def busy_share(self, seconds: float) -> float:
        """Mean share of the pool's threads busy fetching in ``[0, seconds)``."""
        busy = sum(max(0.0, min(e, seconds) - max(s, 0.0)) for s, e in self.spans)
        return busy / (len(self.threads) * seconds)


def _submit(engine, frame, stream_id):
    import jax

    with jax.profiler.TraceAnnotation("client.submit"):
        return engine.submit(frame, stream_id=stream_id)


def run_closed(engine, pool: np.ndarray, outstanding: int, seconds: float, keep,
               fetch_threads: int, on_open=None) -> tuple:
    """Closed loop for ``seconds``; returns ``(outcomes, t0_perf, drained,
    fetcher)``. Frame ``i`` is ``pool[i % len(pool)]``; each outcome's
    ``due`` is when it was sent. This thread submits; each answer that comes
    back hands it one credit for the next submission."""
    import jax

    outcomes = []
    credits: "queue.Queue" = queue.Queue()
    if on_open is not None:
        on_open()
    t0 = time.perf_counter()
    fetch = Fetcher(t0, keep, fetch_threads, on_done=lambda oc: credits.put(None))

    def send(i):
        oc = Outcome(stream=0, index=i, due=time.perf_counter() - t0)
        fut = _submit(engine, pool[i % len(pool)], None)
        oc.sent = oc.due
        outcomes.append(oc)
        fetch.watch(fut, oc)

    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(outstanding):
            send(i)
        i = outstanding
        while True:
            left = seconds - (time.perf_counter() - t0)
            if left <= 0:
                break
            try:
                credits.get(timeout=left)
            except queue.Empty:
                break
            if time.perf_counter() - t0 >= seconds:
                break
            send(i)
            i += 1
    drained = fetch.drain(DRAIN_S)
    fetch.close()
    return outcomes, t0, drained, fetch


def run_open(engine, frames_of, schedule: list, seconds: float, keep, temporal: bool,
             fetch_threads: int, on_open=None) -> tuple:
    """Open loop: submit each arrival when it falls due. ``frames_of(s, k)``
    gives stream ``s``'s frame ``k``. Returns ``(outcomes, t0_perf,
    drained, fetcher)``; each outcome's ``sent - due`` is how late the
    client ran."""
    import jax

    outcomes = [Outcome(stream=a.stream, index=a.index, due=a.due) for a in schedule]
    if on_open is not None:
        on_open()
    t0 = time.perf_counter()
    fetch = Fetcher(t0, keep, fetch_threads)
    with jax.profiler.TraceAnnotation("bench.window"):
        for oc in outcomes:
            wait = oc.due - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            oc.sent = time.perf_counter() - t0
            fut = _submit(engine, frames_of(oc.stream, oc.index),
                          oc.stream if temporal else None)
            fetch.watch(fut, oc)
        time.sleep(max(0.0, seconds - (time.perf_counter() - t0)))
    drained = fetch.drain(DRAIN_S)
    fetch.close()
    return outcomes, t0, drained, fetch
