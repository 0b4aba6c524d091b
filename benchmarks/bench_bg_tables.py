"""Paper Table I / Table II analogues.

Table I: full-HD throughput and cost vs window radius r — the paper's
headline claim is that both are ~independent of r (its FPGA resources and fps
stay flat). Since PR 7 the sweep times the **tuned plan** (`plan_for`'s
roofline-ranked pick — the repo's real hot path) rather than the jnp
reference: the r-independence claim is about the pipelined datapath, and the
pipelined datapath here is the fused Pallas kernel under its auto-tuned
dispatch geometry. Each row records the plan that produced it (backend /
batch_tile / storage precision / provenance, via ``BGPlan.describe``), so
the perf trajectory stays attributable.

The gated ``ratio/bg_plan_tuned_vs_default`` row is the floor on the whole
tuning story: the plan `plan_for` picks for a workload must never be slower
than the heuristic default construction (`BGPlan(cfg)` — kernel-default
batch_tile, no streaming decision). Both sides are timed interleaved in the
same process (the bench_bg_throughput best-of-reps pattern), so the gate is
host-independent. `cache=False` pins the tuned side to the *model's* pick —
the row gates the roofline ranking itself; the measured-cache path is
exercised and verified by ``bench_plan_sweep``.

Table II: cross-implementation speed — exact BF vs BG (batch), BG (streaming),
BG pow2/fixed-point — ns/pixel on one image (the BF is O(r^2) per pixel, the
BG O(1); image sized so the BF finishes in reasonable time).
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.bg_denoise import TABLE1_SWEEP
from repro.core import (
    BGConfig,
    add_gaussian_noise,
    bilateral_filter,
    bilateral_grid_filter,
    bilateral_grid_filter_fixed,
    grid_shape,
    synthetic_batch,
    synthetic_image,
)

# Tuned >= default is the PR-7 acceptance floor: a latency-ranked plan that
# loses to the blind default means the cost model is inverted for this
# geometry. Gate shape: a 32-frame pack at a small frame, where the tuned
# tile (the whole pack, one macro-pipeline sweep) beats the kernel-default
# tile (8 sweeps of 4) by a wide dispatch-amortization margin (~1.3-2x in
# interpret mode), so host noise cannot push the ratio under 1.0.
TUNED_VS_DEFAULT_FLOOR = 1.0
GATE_H, GATE_W, GATE_B = 60, 96, 32
GATE_REPS = 9


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _tuned_vs_default_rows():
    """The gated floor: plan_for's model pick vs the default-constructed
    plan, interleaved best-of-reps on identical frames."""
    from repro.plan import BGPlan, plan_for

    cfg = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
    frames = jnp.asarray(
        add_gaussian_noise(
            synthetic_batch(GATE_B, GATE_H, GATE_W, seed=3), 30.0, seed=4
        )
    ).block_until_ready()
    tuned = plan_for(
        cfg, GATE_H, GATE_W, n_frames=GATE_B, sharded=False, cache=False
    )
    default = BGPlan(cfg=cfg)  # kernel-default tile, no streaming decision

    def run_tuned():
        jax.block_until_ready(tuned(frames))

    def run_default():
        jax.block_until_ready(default(frames))

    run_tuned()  # warm-up / compile
    run_default()
    tt, td = [], []
    for _ in range(GATE_REPS):
        t0 = time.perf_counter()
        run_tuned()
        tt.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_default()
        td.append(time.perf_counter() - t0)
    t_tuned, t_default = min(tt), min(td)
    tag = f"b{GATE_B}_{GATE_H}x{GATE_W}_r{cfg.r}"
    return [
        (
            f"table1/plan_tuned_{tag}",
            t_tuned / GATE_B * 1e6,
            f"fps={GATE_B / t_tuned:.0f} plan={tuned.describe()}",
        ),
        (
            f"table1/plan_default_{tag}",
            t_default / GATE_B * 1e6,
            f"fps={GATE_B / t_default:.0f} plan={default.describe()}",
        ),
        (
            "ratio/bg_plan_tuned_vs_default",
            t_default / t_tuned,
            f"floor={TUNED_VS_DEFAULT_FLOOR} default/tuned dispatch time at "
            f"{tag} (roofline-ranked plan_for pick vs kernel-default "
            f"BGPlan; interleaved best-of-{GATE_REPS})",
        ),
    ]


def run(quick: bool = False):
    from repro.plan import BGPlan, plan_for

    rows = []
    # ---------------- Table I: r sweep at full HD, through the tuned plan
    h, w = (270, 480) if quick else (1080, 1920)
    b = 4 if quick else 2
    noisy = add_gaussian_noise(synthetic_batch(b, h, w, seed=0), 30.0, seed=1)
    times = {}
    for wl in TABLE1_SWEEP:
        cfg = wl.bg
        plan = plan_for(cfg, h, w, n_frames=b, sharded=False, cache=False)
        dt = _time(plan, noisy, reps=2 if quick else 3) / b
        times[cfg.r] = dt
        gx, gy, gz = grid_shape(h, w, cfg)
        rows.append(
            (
                f"table1/bg_fullhd_r{cfg.r}",
                dt * 1e6,
                f"ns_per_pixel={dt*1e9/(h*w):.2f} grid={gx}x{gy}x{gz} "
                f"plan={plan.describe()}",
            )
        )
    flatness = max(times.values()) / min(times.values())
    rows.append(
        (
            "table1/r_independence",
            0.0,
            f"max_over_min_time={flatness:.2f} (paper: ~1.0; tuned-plan "
            f"sweep at b={b})",
        )
    )

    # the gated tuned-vs-default floor (host-independent, quick and full)
    rows.extend(_tuned_vs_default_rows())

    # ---------------- Table II: implementations at a BF-feasible size
    h2, w2 = (96, 128) if quick else (256, 384)
    noisy2 = add_gaussian_noise(synthetic_image(h2, w2), 30.0)
    r, ss, sr = 12, 8.0, 70.0
    cfg = BGConfig(r=r, sigma_s=ss, sigma_r=sr)
    cfg_p2 = BGConfig(r=r, sigma_s=ss, sigma_r=sr, weight_mode="pow2")
    streaming = BGPlan(cfg=cfg, backend="streaming")
    impls = {
        "bf_exact": lambda: bilateral_filter(noisy2, r, ss, sr),
        "bg": lambda: bilateral_grid_filter(noisy2, cfg),
        "bg_streaming": lambda: streaming(noisy2),
        "bg_fixed_pow2": lambda: bilateral_grid_filter_fixed(noisy2, cfg_p2),
    }
    base = None
    for name, fn in impls.items():
        dt = _time(fn, reps=2 if quick else 3)
        if name == "bf_exact":
            base = dt
        rows.append(
            (
                f"table2/{name}",
                dt * 1e6,
                f"ns_per_pixel={dt*1e9/(h2*w2):.2f} speedup_vs_bf={base/dt:.1f}x",
            )
        )
    return rows
