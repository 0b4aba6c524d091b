#!/usr/bin/env python3
"""Run the bilateral-grid denoiser's serving path once on a TPU.

    python chip_smoke.py               # phases (a)-(c) on jax.devices()[0]
    python chip_smoke.py --four-chips  # a 4-device batch-mesh plan vs one chip

Every phase goes through the entry points a user calls (``plan_for`` ->
``BGPlan`` -> ``AsyncFrameEngine`` / ``MultiStreamPacker``) at the paper's
full width, with Pallas kernels compiled for the chip:

  (a) the paper's setting (Table II: 1920x1080 8-bit, r=12, sigma_s=8,
      sigma_r=70): 8 noisy frames through ``AsyncFrameEngine``, against
      ``core.bilateral_grid.bilateral_grid_filter`` run on the same chip;
      the quantized outputs must agree within 1 LSB;
  (b) the same frames through a bf16-storage plan: MSSIM >= 0.98 against the
      fp32 output of (a);
  (c) temporal video, 4 streams x 6 frames at 1080p, alpha=0.6, through
      ``MultiStreamPacker`` inside the engine, against the ``reference``
      backend's temporal plan: outputs within 1 LSB, carries within the
      oracle tolerance of the tests.

``--four-chips`` runs only one phase: 16 frames at 1080p through a 4-device
batch-mesh plan, compared bitwise with the same plan on one device.

A phase fails on any exception, on a result outside its tolerance, and on
any fallback-ladder rung, retry or failed request in the engine. Each phase
serves its frames twice, through a fresh engine each time: a warm-up pass
that pays the engine's compiles, then the timed pass. It prints its plan, the
kernel's compile seconds, both passes' wall seconds and the timed pass's
``EngineStats``; reference times include their compiles. The last
line of stdout is ``{"ok": true, "device": {...}}``, printed only when every
phase passed. With no TPU, or without this checkout's ``src/`` beside the
script, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

LSB_TOL = 1.0
MSSIM_FLOOR = 0.98  # the repo's bf16-vs-fp32 floor (bench_bg_quality.py)
# carry agreement with the staged oracle (tests/test_temporal_fused.py)
CARRY_ATOL, CARRY_RTOL = 2e-2, 1e-3
# hold every micro-batch until it is full, so each phase dispatches one shape
WINDOW_MS = 60_000.0
RESULT_TIMEOUT_S = 600.0


def _paper_cfg():
    from repro.configs.bg_denoise import PAPER_DEFAULT

    return PAPER_DEFAULT.bg


def noisy_frames(n: int, h: int, w: int, seed: int, motion: float = 24.0) -> list:
    """``n`` 8-bit noisy frames (sigma 30, the paper's noise): windows into
    one synthetic scene, ``motion`` pixels apart, with independent noise."""
    from repro.core import add_gaussian_noise
    from repro.data import synthetic_video

    clean = synthetic_video(seed, n, h, w, motion=motion)
    return list(np.asarray(add_gaussian_noise(clean, 30.0, seed=seed + 1000)))


def _compile_s(fn, *args) -> float:
    import jax

    shapes = [jax.ShapeDtypeStruct(np.shape(a), a.dtype) for a in args]
    t0 = time.perf_counter()
    fn.lower(*shapes).compile()
    return time.perf_counter() - t0


def _serve(make_engine, frames, stream_ids=None):
    """Serve ``frames`` twice, each time through a fresh engine from
    ``make_engine()``: a warm-up pass that pays the engine's own compiles,
    then the timed pass. Returns the timed pass's outputs, wall seconds,
    ``EngineStats`` and engine, and the warm-up's wall seconds."""
    runs = []
    for _ in range(2):
        engine = make_engine()
        t0 = time.perf_counter()
        if stream_ids is None:
            futs = [engine.submit(f) for f in frames]
        else:
            futs = [engine.submit(f, stream_id=s)
                    for f, s in zip(frames, stream_ids)]
        outs = [np.asarray(f.result(timeout=RESULT_TIMEOUT_S)) for f in futs]
        wall_s = time.perf_counter() - t0
        engine.close()
        runs.append((outs, wall_s, engine.stats(), engine))
    return runs[1] + (runs[0][1],)


def _engine_clean(stats, n: int) -> bool:
    """No fallback rung, retry, failure or shed request: what came back is
    what the plan's own kernel computed."""
    return (
        stats.completed == n
        and stats.failed == 0
        and stats.retries == 0
        and stats.fallbacks == 0
        and stats.shed == 0
    )


def _lsb_report(outs, refs) -> dict:
    diff = np.abs(np.stack(outs) - np.stack(refs))
    return {
        "max_abs_diff": float(diff.max()),
        "differing_pixel_share": float((diff > 0).mean()),
    }


def _plan_report(plan) -> dict:
    return {"plan": plan.describe(), "interpret_pinned": plan.interpret}


def phase_paper(h: int = 1080, w: int = 1920, n_frames: int = 8, seed: int = 0):
    """(a): returns ``(result, frames, outputs)``."""
    import jax
    import jax.numpy as jnp

    from repro.core.bilateral_grid import bilateral_grid_filter
    from repro.plan import plan_for
    from repro.serving import AsyncFrameEngine

    cfg = _paper_cfg()
    frames = noisy_frames(n_frames, h, w, seed)
    plan = plan_for(cfg, h, w, n_frames=n_frames, sharded=False, cache=False)
    compile_s = _compile_s(plan.executable(), np.stack(frames))
    outs, wall_s, stats, _, warmup_s = _serve(
        lambda: AsyncFrameEngine(plan=plan, max_batch=n_frames,
                                 batch_window_ms=WINDOW_MS),
        frames,
    )

    ref_fn = jax.jit(jax.vmap(lambda im: bilateral_grid_filter(im, cfg)))
    t0 = time.perf_counter()
    refs = list(np.asarray(ref_fn(jnp.asarray(np.stack(frames)))))
    ref_s = time.perf_counter() - t0
    cmp = _lsb_report(outs, refs)
    ok = (
        cmp["max_abs_diff"] <= LSB_TOL
        and _engine_clean(stats, n_frames)
        and plan.interpret is None
    )
    result = {
        "ok": ok, **_plan_report(plan), "frames": [n_frames, h, w],
        "compile_s": compile_s, "warmup_s": warmup_s, "wall_s": wall_s,
        "reference_s": ref_s, **cmp, "engine": stats.as_dict(),
    }
    return result, frames, outs


def phase_bf16(frames, fp32_outs):
    """(b): the frames of (a) through a bf16-storage plan."""
    from repro.core import mssim
    from repro.plan import plan_for
    from repro.serving import AsyncFrameEngine

    cfg = _paper_cfg()
    n = len(frames)
    h, w = frames[0].shape
    plan = plan_for(cfg, h, w, n_frames=n, precision="bf16", sharded=False, cache=False)
    compile_s = _compile_s(plan.executable(), np.stack(frames))
    outs, wall_s, stats, _, warmup_s = _serve(
        lambda: AsyncFrameEngine(plan=plan, max_batch=n,
                                 batch_window_ms=WINDOW_MS),
        frames,
    )
    scores = [float(mssim(a, b)) for a, b in zip(outs, fp32_outs)]
    ok = (
        min(scores) >= MSSIM_FLOOR
        and plan.precision == "bf16"
        and _engine_clean(stats, n)
        and plan.interpret is None
    )
    return {
        "ok": ok, **_plan_report(plan), "compile_s": compile_s,
        "warmup_s": warmup_s, "wall_s": wall_s,
        "mssim_vs_fp32_min": min(scores), **_lsb_report(outs, fp32_outs),
        "engine": stats.as_dict(),
    }


def phase_temporal(h: int = 1080, w: int = 1920, n_streams: int = 4,
                   n_frames: int = 6, alpha: float = 0.6, seed: int = 0):
    """(c): warm temporal streams in the engine vs the reference plan."""
    import jax.numpy as jnp

    from repro.plan import plan_for
    from repro.serving import AsyncFrameEngine
    from repro.video import MultiStreamPacker, carry_shape

    cfg = _paper_cfg()
    video = [
        noisy_frames(n_frames, h, w, seed + s, motion=1.5)
        for s in range(n_streams)
    ]
    plan = plan_for(cfg, h, w, n_frames=n_streams, temporal=True,
                    sharded=False, cache=False)
    ref_plan = plan_for(cfg, h, w, n_frames=n_streams, temporal=True,
                        backend="reference", sharded=False, cache=False)

    def open_streams(p):
        packer = MultiStreamPacker(plan=p)
        for s in range(n_streams):
            packer.open(s, alpha=alpha)
        return packer

    # the executable every pack dispatches (the packer's tile, temporal form)
    pack_plan = plan.with_tile(plan.tile_for(n_streams)).as_temporal(True)
    compile_s = _compile_s(
        pack_plan.executable(),
        np.zeros((n_streams, h, w), np.float32),
        jnp.zeros((n_streams,) + carry_shape(h, w, cfg), plan.storage_dtype),
        np.zeros((n_streams,), np.float32),
    )
    order = [(s, t) for t in range(n_frames) for s in range(n_streams)]
    outs, wall_s, stats, engine, warmup_s = _serve(
        lambda: AsyncFrameEngine(packer=open_streams(plan),
                                 max_batch=n_streams, batch_window_ms=WINDOW_MS),
        [video[s][t] for s, t in order], [s for s, _ in order],
    )
    packer, ref_packer = engine.packer, open_streams(ref_plan)
    refs = {}
    for t in range(n_frames):
        got = ref_packer.pack({s: video[s][t] for s in range(n_streams)})
        refs.update({(s, t): np.asarray(got[s]) for s in got})
    cmp = _lsb_report(outs, [refs[k] for k in order])
    carry_ok = True
    carry_err = 0.0
    for s in range(n_streams):
        c = np.asarray(packer.sessions[s].carry, np.float32)
        r = np.asarray(ref_packer.sessions[s].carry, np.float32)
        carry_ok &= bool(np.all(np.abs(c - r) <= CARRY_ATOL + CARRY_RTOL * np.abs(r)))
        carry_err = max(carry_err, float(np.abs(c - r).max()))
    ok = (
        cmp["max_abs_diff"] <= LSB_TOL
        and carry_ok
        and stats.dispatches == n_frames
        and _engine_clean(stats, n_streams * n_frames)
        and plan.interpret is None
    )
    return {
        "ok": ok, **_plan_report(pack_plan), "streams": n_streams,
        "frames_per_stream": n_frames, "alpha": alpha, "compile_s": compile_s,
        "warmup_s": warmup_s, "wall_s": wall_s, **cmp, "carry_max_abs_diff": carry_err,
        "carry_within_tolerance": carry_ok, "engine": stats.as_dict(),
    }


def phase_four_chips(h: int = 1080, w: int = 1920, n_frames: int = 16, seed: int = 0):
    """16 frames through a 4-device batch-mesh plan in the engine, bitwise
    against the same plan on one device."""
    from repro.plan import plan_for
    from repro.serving import AsyncFrameEngine
    from repro.serving.device_stages import place_rows
    from repro.sharding.bg_shard import batch_mesh

    cfg = _paper_cfg()
    frames = noisy_frames(n_frames, h, w, seed)
    plan = plan_for(cfg, h, w, n_frames=n_frames, mesh=batch_mesh(4), cache=False)
    single = plan.with_options(mesh=None)
    # the engine pads a batch to a multiple of the mesh before dispatch
    compile_s = _compile_s(plan.executable(), place_rows(frames, plan.mesh)[0])
    outs, wall_s, stats, _, warmup_s = _serve(
        lambda: AsyncFrameEngine(plan=plan, max_batch=n_frames,
                                 batch_window_ms=WINDOW_MS),
        frames,
    )
    t0 = time.perf_counter()
    refs = list(np.asarray(single(np.stack(frames))))
    single_s = time.perf_counter() - t0
    bitwise = all(np.array_equal(a, b) for a, b in zip(outs, refs))
    ok = (
        bitwise
        and plan.mesh_size == 4
        and _engine_clean(stats, n_frames)
        and plan.interpret is None
    )
    return {
        "ok": ok, **_plan_report(plan), "frames": [n_frames, h, w],
        "compile_s": compile_s, "warmup_s": warmup_s, "wall_s": wall_s,
        "single_device_s": single_s,
        "bitwise_equal": bitwise, **_lsb_report(outs, refs),
        "engine": stats.as_dict(),
    }


def _run(name, fn, *args):
    """Run one phase; returns its result (None when it raised)."""
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    except Exception:
        traceback.print_exc()
        print(f"[{name}] FAILED with an exception after "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        return None
    result = out[0] if isinstance(out, tuple) else out
    print(f"[{name}] " + json.dumps(result), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device batch-mesh phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    print(f"# device {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    if args.four_chips:
        results = [_run("four_chips", phase_four_chips)]
    else:
        with jax.default_device(devices[0]):
            a = _run("a_paper", phase_paper)
            b = a and _run("b_bf16", phase_bf16, a[1], a[2])
            c = _run("c_temporal", phase_temporal)
        results = [a and a[0], b, c]
    if not all(r and r["ok"] for r in results):
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
