"""Fused temporal kernel: the in-VMEM grid EMA inside the macro-pipeline.

The contracts under test (tentpole of PR 4):

  * ``alpha == 0`` rows of a temporal dispatch are *bit-identical* to the
    plain fused kernel — including inside mixed packs, so cold-stream bits
    never depend on which warm streams share the micro-batch;
  * the warm path tracks the staged jnp oracle (``grid_create -> grid_blur
    -> EMA -> slice``) to <= 5e-3 pre-quantization over chained ragged
    packs, and the carries track too;
  * ``h % r == 0`` runs the extra carry drain step: every one of the gx
    carry planes is emitted (the last plane is TI-inert but the EMA
    recursion must advance it) and the image output is untouched;
  * a mixed cold/warm/first-frame pack is ONE ``temporal_denoise`` dispatch
    through the packer;
  * carry rows are per-stream isolated at the kernel level;
  * the sharded temporal call matches the single-device call on 1 vs 8 mesh
    devices — image output bitwise, carries to <= 1 ulp (stream axis
    sharded, carries travel with their stream, zero collectives) — closing
    the ROADMAP "temporal path is single-host" item.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise
from repro.core.bilateral_grid import quantize_intensity
from repro.data import synthetic_video
from repro.kernels import bg_fused
from repro.plan import BGPlan
from repro.sharding.bg_shard import batch_mesh
from repro.video import MultiStreamPacker, blurred_grid_batch, carry_shape, temporal_denoise

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)

# ragged (h % r != 0) and stripe-aligned (h % r == 0, extra drain step) packs
PACK_SHAPES = [((45, 55), 3), ((33, 47), 5), ((36, 48), 4)]


def _noisy_stack(n, h, w, seed=0):
    vid = synthetic_video(seed, n, h, w, motion=1.5)
    return jnp.stack(
        [add_gaussian_noise(vid[t], 30.0, seed=seed + 10 * t) for t in range(n)]
    )


def _zero_carry(n, h, w, cfg=CFG):
    return jnp.zeros((n,) + carry_shape(h, w, cfg), jnp.float32)


@pytest.mark.parametrize("shape,n", PACK_SHAPES)
def test_alpha0_rows_bit_identical_in_mixed_pack(shape, n):
    """Cold rows of a warm pack == the plain fused kernel, bitwise — the
    property that lets the packer issue ONE dispatch for mixed packs."""
    h, w = shape
    frames = _noisy_stack(n, h, w)
    alpha = jnp.asarray([0.0 if i % 2 == 0 else 0.6 for i in range(n)])
    out, new_carry = bg_fused(
        frames, CFG, interpret=True, carry=_zero_carry(n, h, w), alpha=alpha
    )
    assert new_carry.shape == (n,) + carry_shape(h, w, CFG)
    ref = bg_fused(frames, CFG, interpret=True)
    for i in range(n):
        if i % 2 == 0:
            np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(ref[i]))

    # an all-zero-alpha temporal dispatch is bit-identical on every row
    out0, _ = bg_fused(
        frames,
        CFG,
        interpret=True,
        carry=_zero_carry(n, h, w),
        alpha=jnp.zeros((n,)),
    )
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(ref))


def test_alpha0_new_carry_is_own_blurred_grid():
    """At a == 0 the EMA reduces to B_t: the carry output is the frame's own
    blurred homogeneous grid (vs the hoisted staged GC+GF, float tolerance —
    kernel one-hot matmuls vs scatter/conv reassociate)."""
    frames = _noisy_stack(3, 45, 55)
    _, new_carry = bg_fused(
        frames,
        CFG,
        interpret=True,
        carry=_zero_carry(3, 45, 55),
        alpha=jnp.zeros((3,)),
    )
    ref = blurred_grid_batch(frames, CFG)
    np.testing.assert_allclose(
        np.asarray(new_carry), np.asarray(ref), atol=2e-2, rtol=1e-4
    )


@pytest.mark.parametrize("shape,n", PACK_SHAPES)
def test_warm_chained_matches_staged_oracle(shape, n):
    """Chained warm packs (the EMA recursion) track the staged oracle to
    <= 5e-3 pre-quantization, carries included — over ragged shapes and the
    h % r == 0 drain-step case, with mixed per-stream alphas."""
    h, w = shape
    alpha = np.asarray([0.0, 0.4, 0.8, 0.6, 0.3][:n], np.float32)
    cf = cs = _zero_carry(n, h, w)
    for t in range(4):
        frames = _noisy_stack(n, h, w, seed=31 * t)
        of, cf = temporal_denoise(
            frames, carry=cf, alpha=alpha,
            plan=BGPlan(cfg=CFG, mesh=batch_mesh(), interpret=True,
                        quantize_output=False),
        )
        os_, cs = temporal_denoise(
            frames, carry=cs, alpha=alpha,
            plan=BGPlan(cfg=CFG, backend="reference", quantize_output=False),
        )
        np.testing.assert_allclose(
            np.asarray(of), np.asarray(os_), atol=5e-3, rtol=0.0
        )
        np.testing.assert_allclose(
            np.asarray(cf), np.asarray(cs), atol=2e-2, rtol=1e-3
        )


def test_h_divisible_emits_all_carry_planes():
    """h % r == 0: gx = h//r + 2 and the last blurred plane only exists on
    the extra drain step — it must land in the carry (matching the staged
    oracle's plane) while the image output stays bit-identical to the plain
    fused kernel at alpha 0."""
    h, w = 36, 48
    assert h % CFG.r == 0
    frames = _noisy_stack(2, h, w)
    gx = carry_shape(h, w, CFG)[0]
    _, new_carry = bg_fused(
        frames, CFG, interpret=True, carry=_zero_carry(2, h, w),
        alpha=jnp.zeros((2,)),
    )
    ref = blurred_grid_batch(frames, CFG)
    # the drain-step plane specifically (TI never reads it, the EMA must)
    assert float(np.abs(np.asarray(ref[:, gx - 1])).max()) > 0.0
    np.testing.assert_allclose(
        np.asarray(new_carry[:, gx - 1]),
        np.asarray(ref[:, gx - 1]),
        atol=2e-2,
        rtol=1e-4,
    )


def test_mixed_pack_is_single_dispatch(monkeypatch):
    """Cold + warm + first-frame streams in one pack -> exactly one
    temporal_denoise dispatch (the old packer split mixed packs in two)."""
    import repro.video.session as session_mod

    calls = []
    real = session_mod.temporal_denoise

    def counting(*args, **kwargs):
        calls.append(kwargs.get("alpha"))
        return real(*args, **kwargs)

    monkeypatch.setattr(session_mod, "temporal_denoise", counting)
    packer = MultiStreamPacker(
        BGPlan(cfg=CFG, mesh=batch_mesh(), interpret=True)
    )
    packer.open("cold", alpha=0.0)
    packer.open("warm", alpha=0.6)
    packer.open("fresh", alpha=0.4)  # first frame: no history yet
    frames = _noisy_stack(3, 33, 47)
    packer.pack({"cold": frames[0], "warm": frames[1], "fresh": frames[2]})
    assert len(calls) == 1
    packer.pack({"cold": frames[2], "warm": frames[0], "fresh": frames[1]})
    assert len(calls) == 2  # still one per pack once everyone is warm
    assert packer.sessions["cold"].carry is None
    assert packer.sessions["warm"].carry is not None
    assert packer.sessions["fresh"].carry is not None


def test_kernel_level_carry_isolation():
    """Row i of a temporal pack == the same stream dispatched alone. The
    blend may be contracted into an FMA differently per dispatch geometry
    (see the blend comment in bg_fused), so across geometries the quantized
    image is per-stream *bitwise* (batch composition never moves a served
    pixel), while the unquantized image and the carry agree to a few ulp.
    Identical geometry gives identical bits."""
    n, h, w = 3, 45, 55
    frames = _noisy_stack(n, h, w, seed=9)
    rng = np.random.default_rng(0)
    carry = jnp.asarray(
        rng.uniform(0.0, 4.0, (n,) + carry_shape(h, w, CFG)).astype(np.float32)
    )
    alpha = jnp.asarray([0.3, 0.6, 0.9])
    out, new_carry = bg_fused(
        frames, CFG, interpret=True, batch_tile=1, carry=carry, alpha=alpha
    )
    for i in range(n):
        oi, ci = bg_fused(
            frames[i : i + 1],
            CFG,
            interpret=True,
            batch_tile=1,
            carry=carry[i : i + 1],
            alpha=alpha[i : i + 1],
        )
        np.testing.assert_array_equal(
            np.asarray(quantize_intensity(out[i], CFG)),
            np.asarray(quantize_intensity(oi[0], CFG)),
        )
        np.testing.assert_array_max_ulp(
            np.asarray(out[i]), np.asarray(oi[0]), maxulp=4
        )
        np.testing.assert_array_max_ulp(
            np.asarray(new_carry[i]), np.asarray(ci[0]), maxulp=4
        )
    # identical geometry => identical bits (the reproducibility contract)
    out2, new_carry2 = bg_fused(
        frames, CFG, interpret=True, batch_tile=1, carry=carry, alpha=alpha
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(new_carry), np.asarray(new_carry2))


def test_temporal_argument_validation():
    frames = _noisy_stack(2, 33, 47)
    carry = _zero_carry(2, 33, 47)
    with pytest.raises(ValueError):  # carry without alpha
        bg_fused(frames, CFG, interpret=True, carry=carry)
    with pytest.raises(ValueError):  # alpha without carry
        bg_fused(
            frames, CFG, interpret=True, alpha=jnp.zeros((2,))
        )
    with pytest.raises(ValueError):  # stream_input does not compose
        bg_fused(
            frames, CFG, interpret=True, stream_input=True, carry=carry,
            alpha=jnp.zeros((2,)),
        )
    with pytest.raises(ValueError):  # carry row count mismatch
        bg_fused(
            frames, CFG, interpret=True, carry=carry[:1], alpha=jnp.zeros((2,))
        )
    with pytest.raises(ValueError):  # alpha length mismatch
        bg_fused(
            frames, CFG, interpret=True, carry=carry, alpha=jnp.zeros((3,))
        )


def test_single_frame_squeeze_temporal():
    frame = _noisy_stack(1, 45, 55)[0]
    carry = _zero_carry(1, 45, 55)[0]
    out, new_carry = bg_fused(
        frame, CFG, interpret=True, carry=carry, alpha=jnp.asarray(0.0)
    )
    assert out.shape == frame.shape
    assert new_carry.shape == carry_shape(45, 55, CFG)
    ref = bg_fused(frame, CFG, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def run_sub(body: str, devices: int = 8, timeout: int = 420) -> str:
    """Forced host-device-count subprocess (same pattern as test_bg_sharded)."""
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


def test_temporal_sharded_identical_1_vs_8_devices():
    """The temporal call shards the stream axis over the ("batch",) mesh with
    zero collectives: for ragged stream counts (n % nd != 0, n < nd) the
    8-device *quantized image* is bit-identical to the single-device call,
    while the unquantized image and the carries agree to a few ulp
    (per-shard loop shapes may contract the blend into FMAs differently —
    see bg_fused)."""
    run_sub(
        """
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import BGConfig, add_gaussian_noise
        from repro.data import synthetic_video
        from repro.plan import BGPlan
        from repro.sharding.bg_shard import batch_mesh
        from repro.video import carry_shape

        assert jax.device_count() == 8
        cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
        h, w = 45, 55

        def tplan(nd, quantize=False):
            return BGPlan(cfg=cfg, temporal=True, mesh=batch_mesh(nd),
                          interpret=True, quantize_output=quantize)

        rng = np.random.default_rng(1)
        for n, nd in [(8, 8), (5, 4), (3, 8), (1, 8), (7, 2)]:
            vid = synthetic_video(n, n, h, w, motion=1.5)
            frames = jnp.stack([add_gaussian_noise(vid[t], 30.0, seed=t)
                                for t in range(n)])
            carry = jnp.asarray(rng.uniform(
                0.0, 4.0, (n,) + carry_shape(h, w, cfg)).astype(np.float32))
            alpha = jnp.asarray(rng.uniform(0.0, 0.9, (n,)).astype(np.float32))
            ref_o, ref_c = tplan(1)(frames, carry=carry, alpha=alpha)
            out, new_c = tplan(nd)(frames, carry=carry, alpha=alpha)
            q_ref, _ = tplan(1, True)(frames, carry=carry, alpha=alpha)
            q_out, _ = tplan(nd, True)(frames, carry=carry, alpha=alpha)
            np.testing.assert_array_equal(np.asarray(q_out), np.asarray(q_ref))
            np.testing.assert_array_max_ulp(
                np.asarray(out), np.asarray(ref_o), maxulp=4)
            np.testing.assert_array_max_ulp(
                np.asarray(new_c), np.asarray(ref_c), maxulp=4)
            print(f"OK n={n} nd={nd}")

        # a packer on an 8-device plan: a mixed pack must stay one
        # dispatch and cold rows bit-identical to the per-frame service
        from repro.kernels import bg_fused
        from repro.video import MultiStreamPacker
        packer = MultiStreamPacker(
            BGPlan(cfg=cfg, mesh=batch_mesh(), interpret=True))
        packer.open("cold", alpha=0.0)
        packer.open("warm", alpha=0.6)
        vid = synthetic_video(3, 2, h, w, motion=1.5)
        fr = [jnp.asarray(add_gaussian_noise(vid[t], 30.0, seed=t))
              for t in range(2)]
        from repro.core.bilateral_grid import quantize_intensity
        for t in range(2):
            outs = packer.pack({"cold": fr[t], "warm": fr[t]})
            ref = quantize_intensity(
                bg_fused(fr[t], cfg, interpret=True), cfg)
            np.testing.assert_array_equal(
                np.asarray(outs["cold"]), np.asarray(ref))
        print("OK packer mixed 8dev")
        """
    )
