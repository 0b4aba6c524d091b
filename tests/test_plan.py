"""The plan layer (repro.plan): equivalence matrix + the compiled-executable
cache + construction-time validation + auto-tuning.

Equivalence contract: every backend, temporal form and input shape, reached
through a ``BGPlan`` (built directly, resolved by ``plan_for``, or called by
``temporal_denoise`` / the video packer), gives outputs **bit-identical** to
the pre-refactor code paths. The pre-refactor routes are reconstructed here
from the primitives they composed (``jax.vmap(bilateral_grid_filter)``,
``quantize_intensity(bg_fused_kernel_call(...))``, the staged kernels, the
staged temporal jnp pipeline), so this matrix keeps gating even though the
old layer-local dispatch code is gone.

Cache contract: repeated dispatches of one plan (from any layer) hit one
compiled executable — equal plans share the executable object, and the
executable's jit cache holds exactly one entry per input shape.

Multi-device combos run in a subprocess with a forced 8-device host mesh
(same pattern as test_bg_sharded.py); CI runs this file in the multi-device
job too.
"""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
from repro.core.bilateral_grid import (
    bilateral_grid_filter,
    grid_normalize,
    grid_slice,
    quantize_intensity,
)
from repro.kernels.bg_fused import bg_fused_kernel_call
from repro.kernels.ops import _staged_single
from repro.plan import (
    MAX_AUTO_TILE,
    BGPlan,
    auto_batch_tile,
    auto_stream_input,
    plan_for,
)
from repro.video.session import MultiStreamPacker
from repro.video.temporal import blurred_grid_batch, carry_shape, temporal_denoise

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = BGConfig(r=4, sigma_s=3.0, sigma_r=50.0)
H, W = 19, 26  # ragged wrt r on both axes


def _frames(b, seed=0, h=H, w=W):
    return np.asarray(
        add_gaussian_noise(synthetic_batch(b, h, w, seed=seed), 30.0, seed=seed + 7)
    )


# ------------------------------------------------ pre-refactor compositions
def _pre_reference(imgs):
    return jax.vmap(lambda im: bilateral_grid_filter(im, CFG))(jnp.asarray(imgs))


def _pre_fused(imgs, **kw):
    out = bg_fused_kernel_call(
        jnp.asarray(imgs, jnp.float32), CFG, interpret=True, **kw
    )
    return quantize_intensity(out, CFG)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _pre_temporal_staged(frames, carry, alpha, cfg):
    """Verbatim reconstruction of the pre-plan staged temporal step."""
    frames = frames.astype(jnp.float32)
    blurred = blurred_grid_batch(frames, cfg)
    a = alpha.astype(jnp.float32).reshape((-1, 1, 1, 1, 1))
    new_carry = (1.0 - a) * blurred + a * carry
    grid_f = grid_normalize(new_carry)
    out = jax.vmap(lambda gf, f: grid_slice(gf, f, cfg))(grid_f, frames)
    return quantize_intensity(out, cfg), new_carry


# ------------------------------------------------------- equivalence matrix
@pytest.mark.parametrize("b", [1, 3])
def test_reference_matrix(b):
    imgs = _frames(b)
    ref = np.asarray(_pre_reference(imgs))
    for out in (
        plan_for(CFG, H, W, backend="reference")(imgs),
        BGPlan(cfg=CFG, backend="reference")(imgs),
    ):
        np.testing.assert_array_equal(ref, np.asarray(out))


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("stream", [False, True])
def test_fused_matrix(b, stream):
    imgs = _frames(b, seed=b)
    ref = np.asarray(_pre_fused(imgs, stream_input=stream))
    backend = "fused_streamed" if stream else "fused"
    plan = BGPlan(cfg=CFG, backend=backend, interpret=True)
    resolved = plan_for(CFG, H, W, stream_input=stream, batch_tile=4,
                        sharded=False, interpret=True)
    assert resolved == plan.with_tile(4)  # the kernel's default tile, pinned
    for out in (resolved(imgs), plan(imgs)):
        np.testing.assert_array_equal(ref, np.asarray(out))


def test_single_frame_and_color_matrix():
    # single (h, w) frame through the auto-interpret and pinned plans
    img = _frames(1)[0]
    ref1 = np.asarray(_pre_fused(img))
    np.testing.assert_array_equal(
        ref1, np.asarray(BGPlan(cfg=CFG, backend="fused")(img))
    )
    np.testing.assert_array_equal(
        ref1, np.asarray(BGPlan(cfg=CFG, backend="fused", interpret=True)(img))
    )
    # color (b, h, w, 3): channel->batch folding must match the manual fold
    rgb = np.stack([_frames(2, seed=s) for s in range(3)], axis=-1)
    folded = np.moveaxis(rgb, -1, 1).reshape(6, H, W)
    ref = np.asarray(_pre_fused(folded)).reshape(2, 3, H, W)
    ref = np.moveaxis(ref, 1, -1)
    plan = BGPlan(cfg=CFG, backend="fused", interpret=True)
    np.testing.assert_array_equal(
        ref, np.asarray(BGPlan(cfg=CFG, backend="fused")(rgb))
    )
    np.testing.assert_array_equal(ref, np.asarray(plan(rgb)))


def test_staged_matrix():
    imgs = _frames(2, seed=5)
    ref_b = quantize_intensity(
        jax.vmap(lambda im: _staged_single(im, CFG, True))(
            jnp.asarray(imgs, jnp.float32)
        ),
        CFG,
    )
    plan = BGPlan(cfg=CFG, backend="staged", interpret=True)
    out_b = plan(imgs)
    np.testing.assert_array_equal(np.asarray(ref_b), np.asarray(out_b))
    # single frame: the pre-plan route did NOT vmap
    ref_1 = quantize_intensity(
        _staged_single(jnp.asarray(imgs[0], jnp.float32), CFG, True), CFG
    )
    out_1 = plan(imgs[0])
    np.testing.assert_array_equal(np.asarray(ref_1), np.asarray(out_1))


@pytest.mark.parametrize("b", [1, 3])
def test_streaming_matrix(b):
    imgs = _frames(b, seed=11)
    plan = BGPlan(cfg=CFG, backend="streaming")
    out = plan(imgs)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(plan_for(CFG, H, W, backend="streaming", sharded=False)(imgs)),
    )
    # the streaming scan is exactly the whole-image reference
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(_pre_reference(imgs))
    )


@pytest.mark.parametrize("n", [1, 3])
def test_temporal_fused_matrix(n):
    frames = _frames(n, seed=21)
    carry = np.asarray(
        blurred_grid_batch(jnp.asarray(_frames(n, seed=22)), CFG)
    )
    alpha = np.linspace(0.0, 0.7, n).astype(np.float32)  # mixed cold/warm
    ref_out, ref_carry = bg_fused_kernel_call(
        jnp.asarray(frames, jnp.float32),
        CFG,
        interpret=True,
        carry=jnp.asarray(carry),
        alpha=jnp.asarray(alpha),
    )
    ref_out = np.asarray(quantize_intensity(ref_out, CFG))
    # plan_for's route, single-device (carry bits are only ulp-stable across
    # dispatch geometries — the PR-4 contract; mesh plans are gated in the
    # multi-device subprocess test with the atol'd carry), the tile pinned
    # to the kernel default's clamp
    resolved = plan_for(CFG, H, W, n_frames=n, temporal=True, batch_tile=n,
                        sharded=False, interpret=True)
    out_l, carry_l = resolved(frames, carry=carry, alpha=alpha)
    # the base plan through temporal_denoise (same geometry -> carry bitwise)
    plan = BGPlan(cfg=CFG, backend="fused", interpret=True)
    out_p, carry_p = temporal_denoise(frames, carry=carry, alpha=alpha, plan=plan)
    direct = plan.with_options(temporal=True)(
        frames, carry=carry, alpha=jnp.asarray(alpha)
    )
    for out, new_c in ((out_l, carry_l), (out_p, carry_p), direct):
        np.testing.assert_array_equal(ref_out, np.asarray(out))
        np.testing.assert_array_equal(np.asarray(ref_carry), np.asarray(new_c))


def test_temporal_staged_matrix():
    n = 3
    frames = _frames(n, seed=31)
    carry = np.asarray(blurred_grid_batch(jnp.asarray(_frames(n, seed=32)), CFG))
    alpha = np.asarray([0.0, 0.4, 0.8], np.float32)
    ref_out, ref_carry = _pre_temporal_staged(
        jnp.asarray(frames), jnp.asarray(carry), jnp.asarray(alpha), CFG
    )
    out, new_c = temporal_denoise(
        frames, plan=BGPlan(cfg=CFG, backend="reference"), carry=carry,
        alpha=alpha,
    )
    np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(ref_carry), np.asarray(new_c))


def test_temporal_cold_shortcut_matches_per_frame():
    frames = _frames(2, seed=41)
    plan = BGPlan(cfg=CFG, backend="fused", interpret=True)
    out, carry = temporal_denoise(frames, alpha=0.0, plan=plan)
    assert carry is None  # nothing temporal materialized
    np.testing.assert_array_equal(np.asarray(_pre_fused(frames)), np.asarray(out))


def test_packer_asks_plan_for_tile():
    """A packer on plan_for's plan asks it for the tile and matches a packer
    on a plan with the tile pinned by hand bit-for-bit on the image."""
    n = 3
    plan = plan_for(CFG, H, W, n_frames=n, temporal=True, sharded=False,
                    interpret=True)
    assert plan.batch_tile == n  # whole pack in one macro-pipeline sweep
    pinned = MultiStreamPacker(
        BGPlan(cfg=CFG, backend="fused", batch_tile=n, interpret=True)
    )
    modern = MultiStreamPacker(plan=plan)
    for p in (pinned, modern):
        for s in range(n):
            p.open(s, alpha=0.5)
    for t in range(3):
        frames = {s: _frames(1, seed=100 * t + s)[0] for s in range(n)}
        out_l = pinned.pack(frames)
        out_m = modern.pack(frames)
        for s in range(n):
            np.testing.assert_array_equal(
                np.asarray(out_l[s]), np.asarray(out_m[s])
            )


# ------------------------------------------------------- executable caching
def test_equal_plans_share_one_executable():
    p1 = BGPlan(cfg=CFG, backend="fused", interpret=True)
    p2 = BGPlan(cfg=CFG, backend="fused", interpret=True)
    assert p1 == p2 and hash(p1) == hash(p2)
    assert p1.executable() is p2.executable()
    assert p1.executable() is not BGPlan(
        cfg=CFG, backend="fused", interpret=True, quantize_output=False
    ).executable()


def test_repeat_dispatches_hit_one_compiled_executable():
    plan = BGPlan(
        cfg=BGConfig(r=5, sigma_s=3.0, sigma_r=55.0),
        backend="fused",
        interpret=True,
    )
    fn = plan.executable()
    imgs = _frames(2, seed=51)
    for _ in range(3):
        jax.block_until_ready(plan(imgs))
    assert fn._cache_size() == 1  # one executable for repeat dispatches
    # layers share it: the video entry's cold path dispatches the same plan
    jax.block_until_ready(temporal_denoise(imgs, plan=plan)[0])
    assert fn._cache_size() == 1
    # a new batch shape is a new executable entry, nothing more
    jax.block_until_ready(plan(_frames(3, seed=52)))
    assert fn._cache_size() == 2


# ----------------------------------------------------- construction errors
def test_batch_tile_validated_at_construction():
    for bad in (0, -2, 1.5, 2.0, True):
        with pytest.raises(ValueError, match="batch_tile"):
            BGPlan(cfg=CFG, backend="fused", batch_tile=bad)
    with pytest.raises(ValueError, match="batch_tile"):
        bg_fused_kernel_call(jnp.zeros((2, H, W)), CFG, batch_tile=0)
    with pytest.raises(ValueError, match="batch_tile"):
        bg_fused_kernel_call(jnp.zeros((2, H, W)), CFG, batch_tile=1.5)


def test_invalid_combinations_rejected_at_construction():
    with pytest.raises(ValueError, match="stream_input"):
        BGPlan(cfg=CFG, backend="fused_streamed", temporal=True)
    with pytest.raises(ValueError, match="backend"):
        BGPlan(cfg=CFG, backend="warp_drive")
    with pytest.raises(ValueError, match="temporal"):
        BGPlan(cfg=CFG, backend="streaming", temporal=True)
    with pytest.raises(ValueError, match="paper"):
        BGPlan(
            cfg=BGConfig(r=4, sigma_s=3.0, sigma_r=50.0, normalize_mode="classic"),
            backend="fused",
        )
    # non-temporal plans reject temporal operands and vice versa
    plan = BGPlan(cfg=CFG, backend="fused", interpret=True)
    with pytest.raises(ValueError, match="temporal"):
        plan(np.zeros((1, H, W)), carry=np.zeros((1,) + carry_shape(H, W, CFG)))
    with pytest.raises(ValueError, match="carry"):
        plan.with_options(temporal=True)(np.zeros((1, H, W)))


def test_plan_for_mesh_divisibility_error():
    if jax.device_count() > 1:
        mesh = None  # auto-mesh path exercises the same check
        with pytest.raises(ValueError, match="batch_tile"):
            plan_for(CFG, H, W, n_frames=8, batch_tile=8, mesh=mesh)
    else:
        # single device: any tile <= n is fine; the divisibility check needs
        # a real mesh, exercised in the multi-device subprocess test below
        p = plan_for(CFG, H, W, n_frames=8, batch_tile=8)
        assert p.batch_tile == 8


# ------------------------------------------------------------- auto-tuning
def test_auto_tuner_geometry_rules():
    paper = BGConfig(r=12, sigma_s=8.0, sigma_r=70.0)
    # full-HD at paper radius: doubled input blocks blow the auto-pipelining
    # threshold -> manual two-slot DMA
    assert auto_stream_input(paper, 1080, 1920)
    assert plan_for(paper, 1080, 1920, sharded=False).backend == "fused_streamed"
    # small service frames: default auto-pipelined path
    assert not auto_stream_input(CFG, 96, 128)
    assert plan_for(CFG, 96, 128, sharded=False).backend == "fused"
    # temporal never streams input
    assert (
        plan_for(paper, 1080, 1920, temporal=True, sharded=False).backend
        == "fused"
    )

    # tile shrinks monotonically with frame width and respects the caps
    small = auto_batch_tile(CFG, 60, 96)
    big = auto_batch_tile(CFG, 1080, 1920)
    assert 1 <= big <= small <= MAX_AUTO_TILE
    assert auto_batch_tile(CFG, 60, 96, n_frames=3) == 3  # pack-capped
    assert auto_batch_tile(CFG, 60, 96, n_frames=64, mesh_size=8) == 8
    # full-HD working set forces a small tile (the DEFAULT_BATCH_TILE rule)
    assert auto_batch_tile(paper, 1080, 1920) <= 8


def test_plan_for_fills_concrete_tile():
    p = plan_for(CFG, 60, 96, n_frames=16, sharded=False)
    assert p.batch_tile == 16 and p.backend == "fused"
    assert p.tile_for(16) == 16
    assert p.tile_for(5) == 5  # shrunk pack: clamped to the shard
    assert p.with_tile(5).batch_tile == 5
    assert p.with_tile(16) is p  # no-op variant returns the same plan
    # batch_tile=None plans answer with the kernel default's clamp — the
    # exact geometry the kernel would pick, as an explicit plan decision
    base = BGPlan(cfg=CFG, backend="fused")
    assert base.tile_for(3) == 3 and base.tile_for(64) == 4


def test_plan_for_oracle_backends_stay_single_device():
    # auto-mesh must not crash non-sharding backends on multi-device hosts
    # (regression: plan_for built the mesh before resolving the backend)
    p = plan_for(CFG, H, W, backend="reference")
    assert p.mesh is None
    p = plan_for(CFG, H, W, backend="staged")
    assert p.mesh is None
    p = plan_for(CFG, H, W, temporal=True, backend="reference")
    assert p.mesh is None
    with pytest.raises(ValueError, match="mesh-capable"):
        plan_for(CFG, H, W, backend="reference", sharded=True)


def test_packer_rejects_input_streamed_plan():
    streamed = BGPlan(cfg=CFG, backend="fused_streamed")
    with pytest.raises(ValueError, match="fused_streamed"):
        MultiStreamPacker(plan=streamed)


def test_engines_reject_mismatched_plans():
    from repro.serving import AsyncFrameEngine

    raw = BGPlan(cfg=CFG, backend="fused", quantize_output=False)
    with pytest.raises(ValueError, match="quantized"):
        AsyncFrameEngine(plan=raw)
    # video mode dispatches the packer's plan; a second plan must not be
    # silently ignored
    packer = MultiStreamPacker(BGPlan(cfg=CFG))
    other = BGPlan(cfg=CFG, backend="fused", interpret=True)
    with pytest.raises(ValueError, match="packer"):
        AsyncFrameEngine(plan=other, packer=packer)
    eng = AsyncFrameEngine(packer=packer)
    assert eng.plan is packer.plan
    eng.close()


def test_temporal_plan_broadcasts_scalar_alpha():
    frames = _frames(3, seed=61)
    carry = np.asarray(blurred_grid_batch(jnp.asarray(frames), CFG))
    plan = BGPlan(cfg=CFG, backend="fused", temporal=True, interpret=True)
    out_s, c_s = plan(frames, carry=carry, alpha=0.5)  # scalar: broadcast
    out_v, c_v = plan(
        frames, carry=carry, alpha=np.full((3,), 0.5, np.float32)
    )
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_v))
    np.testing.assert_array_equal(np.asarray(c_s), np.asarray(c_v))
    with pytest.raises(ValueError, match="alpha"):
        plan(frames, carry=carry, alpha=1.5)  # range-checked at dispatch


# ------------------------------------------------------------ multi-device
def run_sub(body: str, devices: int = 8, timeout: int = 420) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(body)
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_plans_bit_identical_multidevice():
    """Mesh plans (fused + temporal) vs the single-device routes, plus the
    plan_for divisibility error, on a forced 8-device host mesh."""
    run_sub(
        """
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
        from repro.kernels.bg_fused import bg_fused_kernel_call
        from repro.core.bilateral_grid import quantize_intensity
        from repro.plan import BGPlan, plan_for
        from repro.sharding.bg_shard import batch_mesh
        from repro.video.temporal import blurred_grid_batch

        assert jax.device_count() == 8
        cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
        h, w = 45, 55
        for b, nd in [(8, 8), (5, 4), (3, 8), (1, 8)]:
            imgs = np.asarray(add_gaussian_noise(
                synthetic_batch(b, h, w, seed=b), 30.0, seed=b + 50))
            ref = quantize_intensity(
                bg_fused_kernel_call(jnp.asarray(imgs), cfg, interpret=True), cfg)
            plan = BGPlan(cfg=cfg, backend="fused", mesh=batch_mesh(nd),
                          interpret=True)
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(plan(imgs)))
            print(f"OK fused b={b} nd={nd}")

        # temporal plan: image bitwise vs single-device, carry to <= ulp
        # (FMA-lane selection differs across dispatch geometries — the PR-4
        # contract)
        n = 6
        frames = np.asarray(add_gaussian_noise(
            synthetic_batch(n, h, w, seed=77), 30.0, seed=78))
        carry = np.asarray(blurred_grid_batch(jnp.asarray(frames), cfg))
        alpha = np.linspace(0.0, 0.7, n).astype(np.float32)
        ref_o, ref_c = bg_fused_kernel_call(
            jnp.asarray(frames), cfg, interpret=True,
            carry=jnp.asarray(carry), alpha=jnp.asarray(alpha))
        tplan = BGPlan(cfg=cfg, backend="fused", temporal=True,
                       mesh=batch_mesh(4), interpret=True,
                       quantize_output=False)
        out, new_c = tplan(frames, carry=carry, alpha=alpha)
        np.testing.assert_array_equal(np.asarray(ref_o), np.asarray(out))
        np.testing.assert_allclose(
            np.asarray(ref_c), np.asarray(new_c), atol=2e-3)
        print("OK temporal plan")

        # plan_for: auto-mesh + per-shard tile + the divisibility error
        p = plan_for(cfg, h, w, n_frames=16)
        assert p.mesh_size == 8 and p.batch_tile == 2, (p.mesh_size, p.batch_tile)
        try:
            plan_for(cfg, h, w, n_frames=16, batch_tile=16)
            raise AssertionError("divisibility error not raised")
        except ValueError as e:
            assert "mesh devices" in str(e)
        print("OK plan_for mesh")
        """
    )


# ------------------------------------------- roofline cost model + plan cache
def test_plan_cost_monotonicity():
    from repro.plan import plan_cost, plan_cost_breakdown

    p = BGPlan(cfg=CFG, backend="fused", batch_tile=4)
    # more pixels / more frames cost more
    assert plan_cost(p, 60, 96, 8) < plan_cost(p, 120, 192, 8)
    assert plan_cost(p, 60, 96, 8) < plan_cost(p, 60, 96, 32)
    # non-increasing in batch_tile at fixed total work (fewer, bigger steps)
    costs = [
        plan_cost(BGPlan(cfg=CFG, backend="fused", batch_tile=t), 60, 96, 16)
        for t in (1, 2, 4, 8, 16)
    ]
    assert all(a >= b for a, b in zip(costs, costs[1:]))
    # the stream-vs-default crossover: the manual-DMA path wins at the paper
    # full-HD radius (saved mask bytes beat the DMA issue cost) and loses at
    # small service frames — the PR-5 256 KiB rule as a derived quantity
    paper = BGConfig(r=12, sigma_s=8.0, sigma_r=70.0)
    fused_hd = BGPlan(cfg=paper, backend="fused", batch_tile=2)
    streamed_hd = BGPlan(cfg=paper, backend="fused_streamed", batch_tile=2)
    assert plan_cost(streamed_hd, 1080, 1920, 4) < plan_cost(fused_hd, 1080, 1920, 4)
    fused_sm = BGPlan(cfg=CFG, backend="fused", batch_tile=4)
    streamed_sm = BGPlan(cfg=CFG, backend="fused_streamed", batch_tile=4)
    assert plan_cost(fused_sm, 60, 96, 8) < plan_cost(streamed_sm, 60, 96, 8)
    # the temporal carry's HBM round-trip is charged
    temporal = BGPlan(cfg=CFG, backend="fused", temporal=True, batch_tile=4)
    assert plan_cost(temporal, 60, 96, 8) > plan_cost(fused_sm, 60, 96, 8)
    bd = plan_cost_breakdown(fused_sm, 60, 96, 8)
    assert bd["total_s"] >= bd["bound_s"] > 0
    assert bd["bound_s"] == max(bd["compute_s"], bd["memory_s"])
    assert bd["flops"] > 0 and bd["hbm_bytes"] > 0 and bd["steps"] > 0
    # oracle backends are ranked too (never preferred over a legal fused plan
    # at equal geometry by the model's structural charges)
    ref = BGPlan(cfg=CFG, backend="reference")
    assert plan_cost(ref, 60, 96, 8) > 0


def test_step_bytes_temporal_carry():
    from repro.core.bilateral_grid import grid_shape
    from repro.plan import step_bytes_per_frame

    base = step_bytes_per_frame(CFG, 60, 96)
    temp = step_bytes_per_frame(CFG, 60, 96, temporal=True)
    _, gy, gz = grid_shape(60, 96, CFG)
    # exactly the double-buffered carry in/out blocks, 4 bytes per element
    assert temp - base == 4 * 8 * gz * gy
    # and the tuner sees it: a temporal tile never exceeds the non-temporal
    assert auto_batch_tile(CFG, 60, 96, temporal=True) <= auto_batch_tile(
        CFG, 60, 96
    )


def test_auto_batch_tile_budget_edges():
    from repro.plan import VMEM_STEP_BUDGET_BYTES, step_bytes_per_frame

    paper = BGConfig(r=12, sigma_s=8.0, sigma_r=70.0)
    per = step_bytes_per_frame(paper, 1080, 1920)
    assert auto_batch_tile(paper, 1080, 1920) == max(
        1, min(VMEM_STEP_BUDGET_BYTES // per, MAX_AUTO_TILE)
    )
    # a geometry whose single-frame step blows the budget still gets a legal
    # tile of 1 (the plan must exist; VMEM pressure is the kernel's problem)
    huge = BGConfig(r=16, sigma_s=2.0, sigma_r=10.0)
    assert step_bytes_per_frame(huge, 4320, 7680) > VMEM_STEP_BUDGET_BYTES
    assert auto_batch_tile(huge, 4320, 7680) == 1
    # the mesh cap is the per-device share, rounded UP (ceil): 7 frames on 2
    # devices means one device gets 4
    assert auto_batch_tile(CFG, 60, 96, n_frames=7, mesh_size=2) == 4
    assert auto_batch_tile(CFG, 60, 96, n_frames=64, mesh_size=8) == 8


def test_plan_serialization_round_trip():
    import json as _json

    p = plan_for(
        CFG, 60, 96, n_frames=16, sharded=False, interpret=True,
        quantize_output=False,
    )
    d = p.to_json()
    assert _json.loads(_json.dumps(d)) == d  # JSON-clean payload
    q = BGPlan.from_json(d)
    assert q == p
    assert q.plan_hash() == p.plan_hash()
    # the hash vouches for every dispatch decision
    assert p.with_tile(8).plan_hash() != p.plan_hash()
    assert p.with_options(quantize_output=True).plan_hash() != p.plan_hash()
    assert p.as_temporal().plan_hash() != p.plan_hash()
    # a serialized mesh larger than this host is an error, not a silent
    # single-device shrink (the hash would vouch for the wrong geometry)
    import pytest as _pytest

    with _pytest.raises(ValueError, match="device"):
        BGPlan.from_json({**d, "mesh_size": 4096})
    with _pytest.raises(ValueError, match="version"):
        BGPlan.from_json({**d, "version": 99})


def test_serialized_interpret_pin_refused_on_tpu(monkeypatch):
    """A plan shipped from a CPU host (interpret=True) must not run its
    kernels interpreted on a TPU host; interpret=None and a local pin are
    fine."""
    d = BGPlan(cfg=CFG, backend="fused", interpret=True).to_json()
    assert BGPlan.from_json(d).interpret is True  # off-TPU: honoured
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret"):
        BGPlan.from_json(d)
    assert BGPlan.from_json({**d, "interpret": None}).interpret is None
    assert BGPlan(cfg=CFG, backend="fused", interpret=True).interpret is True


# --------------------------------------------------------- mixed precision
def test_precision_validation_and_serialization():
    from repro.plan import PRECISIONS, precision_bytes

    assert PRECISIONS == ("fp32", "bf16")
    assert precision_bytes("fp32") == 4 and precision_bytes("bf16") == 2
    with pytest.raises(ValueError, match="precision"):
        precision_bytes("fp16")
    with pytest.raises(ValueError, match="precision"):
        BGPlan(cfg=CFG, backend="fused", precision="int8")
    # bf16 storage exists only on the kernel/reference family
    with pytest.raises(ValueError, match="precision"):
        BGPlan(cfg=CFG, backend="streaming", precision="bf16")
    p = BGPlan(cfg=CFG, backend="fused", batch_tile=4, precision="bf16")
    assert p.storage_dtype == jnp.bfloat16
    assert np.dtype(p.np_storage_dtype).itemsize == 2
    assert "prec=bf16" in p.describe()
    d = p.to_json()
    assert d["precision"] == "bf16"
    q = BGPlan.from_json(d)
    assert q == p and q.plan_hash() == p.plan_hash()
    # precision participates in the hash (a v1 cache hash cannot vouch)
    p32 = BGPlan(cfg=CFG, backend="fused", batch_tile=4)
    assert p.plan_hash() != p32.plan_hash()
    # pre-precision payloads (no field) deserialize as fp32
    legacy = {k: v for k, v in p32.to_json().items() if k != "precision"}
    assert BGPlan.from_json(legacy) == p32


def test_precision_step_bytes_and_tile():
    from repro.plan import MAX_AUTO_TILE, step_bytes_per_frame

    # bf16 exactly halves every step-bytes term (storage-dtype contract)
    for kw in ({}, {"stream_input": True}, {"temporal": True}):
        base = step_bytes_per_frame(CFG, 60, 96, **kw)
        half = step_bytes_per_frame(CFG, 60, 96, precision="bf16", **kw)
        assert base == 2 * half
    # and the tuner sees it: at the VMEM-capped paper HD geometry the
    # feasible tile at least doubles (floor division can only round up)
    paper = BGConfig(r=12, sigma_s=8.0, sigma_r=70.0)
    a32 = auto_batch_tile(paper, 1080, 1920)
    a16 = auto_batch_tile(paper, 1080, 1920, precision="bf16")
    assert min(2 * a32, MAX_AUTO_TILE) <= a16 <= MAX_AUTO_TILE
    # bf16 plans cost less at equal geometry (halved HBM operand traffic)
    from repro.plan import plan_cost

    f32 = BGPlan(cfg=CFG, backend="fused", batch_tile=4)
    f16 = BGPlan(cfg=CFG, backend="fused", batch_tile=4, precision="bf16")
    assert plan_cost(f16, 60, 96, 8) < plan_cost(f32, 60, 96, 8)


def test_plan_for_precision_modes():
    # the default (precision=None) NEVER silently changes numerics: fp32
    p = plan_for(CFG, 60, 96, n_frames=8, sharded=False, cache=False)
    assert p.precision == "fp32"
    # pinned bf16 is honored
    p16 = plan_for(
        CFG, 60, 96, n_frames=8, sharded=False, cache=False, precision="bf16"
    )
    assert p16.precision == "bf16" and p16.provenance == "model"
    # "auto" lets the roofline rank both; bf16's halved traffic wins on the
    # fused family
    pa = plan_for(
        CFG, 60, 96, n_frames=8, sharded=False, cache=False, precision="auto"
    )
    assert pa.precision == "bf16"
    # "auto" on a non-fused pinned backend degrades to fp32, not an error
    pr = plan_for(
        CFG, 60, 96, backend="staged", cache=False, precision="auto"
    )
    assert pr.precision == "fp32"
    with pytest.raises(ValueError, match="precision"):
        plan_for(CFG, 60, 96, sharded=False, precision="fp64")


def test_bf16_mode_dispatch_invariants():
    """Within bf16 mode the PR's bit-level contracts mirror fp32's: the
    manual-DMA streamed path is bit-identical to the default path, and an
    ``alpha == 0`` temporal blend is the exact identity."""
    imgs = _frames(3, seed=71)
    p16 = BGPlan(cfg=CFG, backend="fused", interpret=True, precision="bf16")
    p16s = BGPlan(
        cfg=CFG, backend="fused_streamed", interpret=True, precision="bf16"
    )
    out16 = np.asarray(p16(imgs))
    np.testing.assert_array_equal(out16, np.asarray(p16s(imgs)))
    # alpha == 0 bit-identity (zero carry, all-cold pack)
    tp = p16.with_options(temporal=True)
    carry = jnp.zeros((3,) + carry_shape(H, W, CFG), p16.storage_dtype)
    out_t, new_c = tp(imgs, carry=carry, alpha=np.zeros(3, np.float32))
    np.testing.assert_array_equal(out16, np.asarray(out_t))
    assert np.asarray(new_c).dtype == p16.np_storage_dtype
    # the staged jnp oracle's bf16 axis tracks the fused path to the
    # quantization-level tolerance (storage rounding only, fp32 accumulate)
    ref16 = BGPlan(cfg=CFG, backend="reference", precision="bf16")
    np.testing.assert_allclose(
        np.asarray(ref16(imgs), np.float32), out16.astype(np.float32),
        atol=2.0,
    )
    # fp32 plans are byte-for-byte unaffected by the precision plumbing
    p32 = BGPlan(cfg=CFG, backend="fused", interpret=True)
    np.testing.assert_array_equal(
        np.asarray(_pre_fused(imgs)), np.asarray(p32(imgs))
    )


def test_plan_provenance_labels():
    # direct construction = the kernel-default heuristic route
    assert BGPlan(cfg=CFG).provenance == "default"
    # free decisions resolved by the roofline ranking
    tuned = plan_for(CFG, 60, 96, n_frames=8, sharded=False, cache=False)
    assert tuned.provenance == "model"
    # everything pinned by the caller
    pinned = plan_for(
        CFG, 60, 96, backend="fused", batch_tile=4, sharded=False
    )
    assert pinned.provenance == "explicit"
    assert "src=model" in tuned.describe()
    # provenance is informational: it must not split plan equality or hashes
    assert tuned.with_options() == tuned
    assert BGPlan(cfg=CFG, backend="fused", batch_tile=8).plan_hash() == (
        plan_for(
            CFG, 60, 96, n_frames=8, sharded=False, cache=False
        ).plan_hash()
    )
