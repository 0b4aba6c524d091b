"""One benchmark cell, end to end: resolve it by name, set it up, drive its
window, check what the window produced against the plain reference, and
reduce what was seen to the cell's metrics.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json    the deployment, as run (plus its ``limits``)
  traffic/<traffic>.json   the arrival pattern and the engine's settings
  metrics/<metric>.py      ``read(ctx)`` -> a number, or None when the run
                           has nothing to read for it

The program under test is imported from ``<checkout>/src``: ``plan_for``,
``AsyncFrameEngine`` and, for temporal cells, ``MultiStreamPacker``.
"""
from __future__ import annotations

import copy
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SAMPLE = 24            # answers compared per run in a stateless cell
SAMPLE_PER_STREAM = 8  # answers compared per stream in a temporal cell
WARMUP_WINDOW_MS = 60_000.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- the manifest
def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def config_path(manifest: dict, name: str, root: str = ROOT) -> str:
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    return os.path.join(root, entry["file"])


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", name + ".json")


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", name + ".py")


def metrics_for(manifest: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in manifest[kind]
            if workload in m.get("workloads", [workload])]


def resolve(workload: str, root: str = ROOT) -> dict:
    """The cell's workload entry, configuration, traffic and metric names."""
    manifest = load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    return {
        "workload": entry,
        "config": _read_json(config_path(manifest, entry["config"], root)),
        "traffic": _read_json(traffic_path(entry["traffic"])),
        "end_to_end": metrics_for(manifest, workload, "end_to_end"),
        "per_layer": metrics_for(manifest, workload, "per_layer"),
    }


def reader(name: str):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_"),
                                                  metric_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _merge(base: dict, over: dict | None) -> dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


# ------------------------------------------------------------------ devices
def chips(n: int):
    """The first ``n`` accelerator devices; raises :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no accelerator (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def host_settings() -> None:
    """The frame server's process-wide host settings. The engine converts
    every frame to a fresh 8.3 MB fp32 array on the host, and numpy advises
    the kernel to back arrays of 4 MiB or more with transparent huge pages;
    where the host's THP mode is ``madvise``, faulting such pages in can
    compact memory and stop every thread of the process. Arrays stay on
    plain pages here."""
    np._core.multiarray._set_madvise_hugepage(False)


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``,
    else at the fixed ``<checkout>/.jax_cache``; small programs too."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts the executables JAX builds or loads from its cache while
    ``active`` is set (``/jax/core/compile/backend_compile_duration``)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            with self._lock:
                self.count += self.active

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """Records the garbage collector's pauses while ``active`` is set."""

    def __init__(self):
        self.active = False
        self.pauses = []
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            if self.active:
                self.pauses.append((info["generation"], time.perf_counter() - self._t))
            self._t = None

    def close(self):
        gc.callbacks.remove(self._on)


# --------------------------------------------------------------------- cell
class Cell:
    """The system under test, built for one configuration and traffic mix."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.bilateral_grid import BGConfig
        from repro.plan import plan_for

        self.config, self.traffic, self.seed = config, traffic, seed
        self.temporal = bool(config.get("temporal"))
        self.max_batch = int(traffic["engine"]["max_batch"])
        mesh = None
        if config.get("chips", 1) > 1:
            from repro.sharding.bg_shard import batch_mesh

            mesh = batch_mesh(config["chips"])
        bg = BGConfig(r=config["r"], sigma_s=config["sigma_s"], sigma_r=config["sigma_r"],
                      intensity_max=config["intensity_max"])
        kw = {"mesh": mesh} if mesh is not None else {"sharded": False}
        self.plan = plan_for(bg, config["height"], config["width"],
                             n_frames=self.max_batch, temporal=self.temporal,
                             precision=config["precision"], cache=False, **kw)

    # ---- frames
    def make_frames(self) -> None:
        """The cell's frame pools, uint8 on the host."""
        from frames import make_streams

        c, t = self.config, self.traffic
        h, w, n = c["height"], c["width"], t["pool_frames"]
        if t["loop"] == "closed":
            self.pool = make_streams(self.seed, n, 1, h, w, 0.0)[:, 0]
        else:
            self.pool = make_streams(self.seed, t["streams"], n, h, w,
                                     c.get("pan_px_per_frame", 0.0))

    def frame_at(self, stream: int, index: int) -> np.ndarray:
        """Stream ``stream``'s frame ``index`` (open loop), or the closed
        loop's frame ``index``."""
        from frames import ping_pong

        if self.traffic["loop"] == "closed":
            return self.pool[index % len(self.pool)]
        return self.pool[stream][ping_pong(index, self.pool.shape[1])]

    # ---- the engine
    def engine(self, max_batch: int, window_ms: float, streams: int = 0):
        from repro.serving import AsyncFrameEngine

        if not self.temporal:
            return AsyncFrameEngine(plan=self.plan, max_batch=max_batch,
                                    batch_window_ms=window_ms)
        from repro.video import MultiStreamPacker

        packer = MultiStreamPacker(plan=self.plan)
        for s in range(streams):
            packer.open(s, alpha=self.config["alpha"])
        return AsyncFrameEngine(packer=packer, max_batch=max_batch,
                                batch_window_ms=window_ms)

    def warm_up(self) -> None:
        """Serve every batch (or pack) length 1..max_batch once, each
        through an engine that holds its batch until it is full, and bring
        the answers to the host: every program the window can run is then
        built. A temporal pack is served cold and then warm."""
        rounds = 2 if self.temporal else 1
        for k in range(1, self.max_batch + 1):
            eng = self.engine(k, WARMUP_WINDOW_MS, streams=k)
            try:
                for r in range(rounds):
                    futs = [eng.submit(self.frame_at(s, r) if self.temporal
                                       else self.frame_at(0, s),
                                       stream_id=s if self.temporal else None)
                            for s in range(k)]
                    for f in futs:
                        np.asarray(f.result(timeout=600))
            finally:
                eng.close()

    def timed_engine(self):
        e = self.traffic["engine"]
        return self.engine(self.max_batch, e["batch_window_ms"],
                           streams=self.traffic.get("streams", 0) if self.temporal else 0)


# ----------------------------------------------------------------- sampling
class Reservoir:
    """Keeps a seeded uniform sample of ``k`` answers in the order they
    arrive (closed loop, where the count is not known in advance)."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng([int(seed), 3])
        self.seen = 0
        self.kept = []
        self.lock = threading.Lock()

    def __call__(self, oc) -> bool:
        with self.lock:
            self.seen += 1
            if len(self.kept) < self.k:
                self.kept.append(oc)
                return True
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.kept[j].output = None
                self.kept[j] = oc
                return True
            return False


def open_sample(schedule: list, seed: int, temporal: bool) -> set:
    """(stream, index) pairs to compare, drawn from the seed; in a temporal
    cell, ``SAMPLE_PER_STREAM`` per stream, each stream's last frame among
    them."""
    rng = np.random.default_rng([int(seed), 4])
    if not temporal:
        pick = rng.choice(len(schedule), size=min(SAMPLE, len(schedule)), replace=False)
        return {(schedule[i].stream, schedule[i].index) for i in pick}
    last = {}
    for a in schedule:
        last[a.stream] = max(last.get(a.stream, -1), a.index)
    out = set()
    for s, n in last.items():
        pick = rng.choice(n + 1, size=min(SAMPLE_PER_STREAM - 1, n + 1), replace=False)
        out |= {(s, int(i)) for i in pick} | {(s, n)}
    return out


# -------------------------------------------------------------------- check
def reference_outputs(cell: Cell, outcomes: list) -> dict:
    """The plain reference's answer for every kept outcome:
    ``{(stream, index): uint8 frame}``."""
    import reference
    from frames import ping_pong

    params = {k: cell.config[k] for k in ("r", "sigma_s", "sigma_r")}
    kept = [oc for oc in outcomes if oc.output is not None]
    if not kept:
        return {}
    if not cell.temporal:
        frames = np.stack([cell.frame_at(oc.stream, oc.index) for oc in kept])
        refs = reference.denoise(frames, params)
        return {(oc.stream, oc.index): r for oc, r in zip(kept, refs)}
    out = {}
    n_pool = cell.pool.shape[1]
    last = {}
    for oc in outcomes:
        last[oc.stream] = max(last.get(oc.stream, -1), oc.index)
    for s, n in sorted(last.items()):
        keep = [oc.index for oc in kept if oc.stream == s]
        if not keep:
            continue
        order = [ping_pong(t, n_pool) for t in range(n + 1)]
        got = reference.temporal_stream(cell.pool[s], order, cell.config["alpha"],
                                        params, keep)
        out.update({(s, t): v for t, v in got.items()})
    return out


def check(outcomes: list, refs: dict, stats: dict, limits: dict) -> dict:
    """The numbers that decide ``correct``, each with its limit."""
    worst = 0.0
    largest = 0.0
    compared = 0
    for oc in outcomes:
        if oc.output is None or oc.error is not None:
            continue
        ref = refs[(oc.stream, oc.index)].astype(np.float32)
        diff = np.abs(np.asarray(oc.output, np.float32) - ref)
        worst = max(worst, float(np.mean(diff != 0)))
        largest = max(largest, float(diff.max()))
        compared += 1
    lost = sum(1 for oc in outcomes if oc.error is not None or not np.isfinite(oc.done))
    return {
        "off_share_worst": {"value": worst if compared else 1.0,
                            "limit": limits["off_share_worst"]},
        "max_abs_diff": {"value": largest if compared else 255.0,
                         "limit": limits["max_abs_diff"]},
        "lost_frames": {"value": lost, "limit": 0},
        "fallbacks": {"value": stats["fallbacks"], "limit": 0},
        "compared_frames": {"value": compared, "limit": 1, "at_least": True},
    }


def passed(checks: dict) -> bool:
    return all(c["value"] >= c["limit"] if c.get("at_least") else c["value"] <= c["limit"]
               for c in checks.values())


# -------------------------------------------------------------------- trace
def start_trace(path: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


def read_trace(path: str, n_devices: int) -> dict:
    import trace_reduce

    events = trace_reduce.load_xplane(path)
    return trace_reduce.reduce(events, n_devices)


# ---------------------------------------------------------------------- run
ENGINE_FIELDS = ("submitted", "completed", "dispatches", "failed", "retries",
                 "fallbacks", "shed", "carry_resets")


def run(workload: str, seed: int, seconds: float, trace: bool, *, overrides=None,
        devices=None, t_start: float | None = None, root: str = ROOT,
        log=print, on_ctx=None) -> dict:
    """Run one cell; returns the result line's object. ``devices`` defaults
    to the chips the cell asks for (:func:`chips`); ``overrides`` merge
    into the configuration (``{"config": {...}, "traffic": {...}}``);
    ``on_ctx`` is handed what the metric readers read."""
    import jax

    import traffic as tr

    t_start = time.perf_counter() if t_start is None else t_start
    spec = resolve(workload, root)
    config = _merge(spec["config"], (overrides or {}).get("config"))
    traffic = _merge(spec["traffic"], (overrides or {}).get("traffic"))
    if devices is None:
        devices = chips(spec["workload"]["chips"])
    counter = CompileCounter()
    pauses = GcPauses()
    try:
        with jax.default_device(devices[0]):
            cell = Cell(config, traffic, seed)
            log(f"# plan {cell.plan.describe()}")
            cell.make_frames()
            cell.warm_up()
            engine = cell.timed_engine()
            # what set-up left behind is long-lived: keep it out of the
            # collector's scans, as a long-running service would
            gc.collect()
            gc.freeze()
            if traffic["loop"] == "closed":
                keep = Reservoir(SAMPLE, seed)
                schedule = None
            else:
                schedule = tr.open_schedule(traffic, seed, seconds)
                wanted = open_sample(schedule, seed, cell.temporal)
                keep = lambda oc: (oc.stream, oc.index) in wanted  # noqa: E731
            trace_dir = os.path.join(root, ".bench_traces", workload)
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                start_trace(trace_dir)
            marks = {}

            def on_open():
                marks["setup_s"] = time.perf_counter() - t_start
                marks["stats0"] = engine.stats()
                counter.active = pauses.active = True

            threads = tr.FETCH_THREADS
            if schedule is None:
                outcomes, t0, drained, fetch = tr.run_closed(
                    engine, cell.pool, traffic["outstanding"], seconds, keep, threads,
                    on_open)
            else:
                outcomes, t0, drained, fetch = tr.run_open(
                    engine, cell.frame_at, schedule, seconds, keep, cell.temporal,
                    threads, on_open)
            counter.active = pauses.active = False
            if trace:
                jax.profiler.stop_trace()
            stats1 = engine.stats()
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
            engine.close()
            s0 = marks["stats0"]
            window = {f: getattr(stats1, f) - getattr(s0, f) for f in ENGINE_FIELDS}
            plan_desc = cell.plan.describe()
            del engine
            cell.plan = None
            gc.collect()
            refs = reference_outputs(cell, outcomes)
    finally:
        counter.close()
        pauses.close()
    checks = check(outcomes, refs, window, config["limits"])
    late = [oc.sent - oc.due for oc in outcomes if schedule is not None]
    ctx = {
        "config": config, "traffic": traffic, "seconds": seconds,
        "outcomes": outcomes, "setup_s": marks["setup_s"], "engine": window,
        "compiles_in_window": counter.count, "device_kind": devices[0].device_kind,
        "n_devices": len(devices), "plan": plan_desc,
        "fetch_busy": fetch.busy_share(seconds), "fetch_threads": threads,
    }
    if on_ctx is not None:
        on_ctx(ctx)
    result = {
        "correct": passed(checks) and drained,
        "attempted": len(outcomes),
        "failed": sum(1 for oc in outcomes if oc.error is not None or not np.isfinite(oc.done)),
        "metrics": {},
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": int(peak)},
    }
    if trace:
        red = read_trace(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = red
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["top_ops"], "idle_gaps": red["idle_gaps"]}
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = reader(m["name"])(ctx)
        if value is None:
            continue
        entry = {"value": value[0] if isinstance(value, tuple) else value, "unit": m["unit"]}
        if isinstance(value, tuple):
            entry["bound"] = value[1]
        result["metrics"][m["name"]] = entry
    log(f"# engine in window {json.dumps(window)}; compiles in window "
        f"{counter.count}; drained {drained}; fetch pool of {threads} busy "
        f"{100.0 * ctx['fetch_busy']}%")
    if late:
        log(f"# client lateness: max {max(late) * 1e3} ms, "
            f"p99 {sorted(late)[int(0.99 * (len(late) - 1))] * 1e3} ms over {len(late)} frames")
    done = sorted(oc.done for oc in outcomes if oc.done <= seconds)
    gaps = np.diff([0.0] + done + [seconds]) if done else [seconds]
    full = [d for g, d in pauses.pauses if g == 2]
    log(f"# longest gap between answers {max(gaps) * 1e3} ms; gc in window: "
        f"{len(pauses.pauses)} collections, longest "
        f"{max((d for _, d in pauses.pauses), default=0.0) * 1e3} ms, "
        f"{len(full)} full ({sum(full) * 1e3} ms)")
    errors = sorted({oc.error for oc in outcomes if oc.error})
    if errors:
        log(f"# errors: {errors[:5]}")
    result["checks"] = checks
    return result
