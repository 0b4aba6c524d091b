"""Frames/sec scaling vs device count for the sharded fused BG pipeline.

The service path (`repro.sharding.bg_shard.bg_denoise_sharded`) shards the
batch axis of the fused kernel over a 1-D mesh with zero collectives, so on
real hardware frames/sec should scale ~linearly with device count. This bench
measures that curve on a *forced 8-device host mesh*
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — off-TPU all eight
"devices" share the same cores and the Pallas kernel runs in interpret mode,
so the CPU curve is a dispatch-correctness/overhead measurement, not a
speedup claim (labeled as such). On a TPU backend the same code path uses the
real chips.

Off-TPU the measurement runs in a subprocess: the parent bench process has
already initialized jax with its default single-device view, and the forced
device count must be set before the first jax import. On a TPU it runs in
this process over the real chips: a chip belongs to one process, and the
parent that has touched JAX already holds them.

Emits two gated ``ratio/`` rows (sharded scaling d8-vs-d2, and sharded-8dev
vs single-device frames/sec) for the hardware-independent regression gate in
run.py — both sides of each ratio come from the same process on the same
host, so the ratios transfer across machines where absolute wall-clock does
not.
"""
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEVICE_COUNTS = (1, 2, 4, 8)
# Gated ratios (both ~1 on a forced host mesh where all "devices" share one
# CPU; > 1 on real chips):
#   * scaling  = fps(d8) / fps(d2): both sides pay the shard_map dispatch
#     cost, so this isolates how the path behaves as the mesh grows — a drop
#     means the dispatch degrades with device count (per-device retracing, a
#     collective sneaking in).
#   * vs_single = fps(d8) / fps(d1): the sharded wrapper against the plain
#     jitted kernel call. The cached+jitted shard_map keeps this ~1 on the
#     host mesh; a collapse means the wrapper cache broke and every dispatch
#     re-traces (the bug class this floor caught during development: 0.008).
SCALING_RATIO_FLOOR = 0.25
VS_SINGLE_RATIO_FLOOR = 0.2

def measure(h, w, r, b, reps, counts):
    """Best-of-``reps`` seconds per ``b``-frame sharded dispatch for each
    device count in ``counts`` that this process can see."""
    import time

    import jax

    from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
    from repro.sharding.bg_shard import batch_mesh, bg_denoise_sharded

    cfg = BGConfig(r=r, sigma_s=4.0, sigma_r=60.0)
    noisy = add_gaussian_noise(synthetic_batch(b, h, w, seed=0), 30.0, seed=1)
    results = []
    for nd in counts:
        if nd > jax.device_count():
            continue
        mesh = batch_mesh(nd)

        def call():
            jax.block_until_ready(bg_denoise_sharded(noisy, cfg, mesh=mesh))

        call()  # warm-up / compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            call()
            ts.append(time.perf_counter() - t0)
        results.append([nd, min(ts)])
    return results


_CHILD = (
    "import json, sys; "
    "from benchmarks.bench_bg_sharded import measure; "
    "print('RESULT ' + json.dumps(measure(*json.loads(sys.argv[1]))))"
)


def _measure_forced_host_mesh(params):
    """``measure`` in a child with 8 forced host devices (CPU hosts only)."""
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in xla_flags:
        xla_flags = f"{xla_flags} --xla_force_host_platform_device_count=8".strip()
    env = dict(
        os.environ,
        XLA_FLAGS=xla_flags,
        PYTHONPATH=os.pathsep.join([os.path.join(REPO_ROOT, "src"), REPO_ROOT]),
    )
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, json.dumps(params)],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
        cwd=REPO_ROOT,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded bench subprocess failed:\n{out.stdout}\n{out.stderr[-3000:]}"
        )
    line = next(l for l in out.stdout.splitlines() if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def run(quick: bool = False):
    h, w, r = (32, 48, 4) if quick else (64, 96, 6)
    b = 8 if quick else 16
    reps = 3 if quick else 5
    params = [h, w, r, b, reps, list(DEVICE_COUNTS)]
    import jax

    if jax.default_backend() == "tpu":
        results = measure(*params)
    else:
        results = _measure_forced_host_mesh(params)

    rows = []
    fps_by_nd = {}
    for nd, t in results:
        fps = b / t
        fps_by_nd[nd] = fps
        scale = f" scale_vs_1dev={fps / fps_by_nd[1]:.2f}x" if 1 in fps_by_nd else ""
        rows.append(
            (
                f"bg_sharded/fused_b{b}_{h}x{w}_d{nd}",
                t / b * 1e6,
                f"fps={fps:.1f}{scale}",
            )
        )
    nd_max = max(fps_by_nd)
    sharded_counts = [nd for nd in fps_by_nd if nd > 1]
    if sharded_counts and min(sharded_counts) < nd_max:
        nd_min = min(sharded_counts)
        rows.append(
            (
                "ratio/bg_sharded_scaling",
                fps_by_nd[nd_max] / fps_by_nd[nd_min],
                f"floor={SCALING_RATIO_FLOOR} fps_d{nd_max}/fps_d{nd_min} "
                f"(~1 on forced host mesh, ~{nd_max // nd_min} on real chips)",
            )
        )
    if 1 in fps_by_nd and nd_max > 1:
        rows.append(
            (
                "ratio/bg_sharded_vs_single",
                fps_by_nd[nd_max] / fps_by_nd[1],
                f"floor={VS_SINGLE_RATIO_FLOOR} fps_d{nd_max}/fps_d1 "
                f"(~1 on forced host mesh, ~{nd_max} on real chips; collapse "
                f"= sharded wrapper re-tracing per dispatch)",
            )
        )
    return rows
