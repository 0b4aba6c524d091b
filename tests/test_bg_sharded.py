"""Batch-axis sharded service path: bit-equivalence with the single-device
fused kernel, for every (batch size, device count) shape class.

Each test runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps its single-device view (per the harness contract, same pattern
as test_distributed.py). Sharding must be numerically invisible:

  * ragged batches (b not divisible by the device count),
  * b < n_devices (idle devices denoising pure padding),
  * b == 1 (a mesh of mostly-idle devices),
  * ``plan_for``'s auto-mesh over all devices,
  * mesh + ``fused_streamed`` (input DMA) composition,

all bit-identical to ``bg_fused_kernel_call`` on the same batch.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def test_sharded_bit_identical_to_single_device():
    """Every (b, ndev) shape class, incl. ragged, b < ndev, b == 1."""
    run_sub(
        """
        import jax, numpy as np
        from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
        from repro.kernels import bg_fused
        from repro.plan import BGPlan, plan_for
        from repro.sharding.bg_shard import batch_mesh

        assert jax.device_count() == 8
        cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)

        def raw_plan(mesh, backend="fused"):
            return BGPlan(cfg=cfg, backend=backend, mesh=mesh,
                          interpret=True, quantize_output=False)

        # ragged frame shape too (h % r != 0, w % r != 0)
        h, w = 45, 55
        for b, nd in [(8, 8), (5, 4), (6, 8), (3, 8), (1, 8), (1, 1), (7, 2)]:
            imgs = add_gaussian_noise(
                synthetic_batch(b, h, w, seed=b), 30.0, seed=b + 50)
            ref = bg_fused(imgs, cfg, interpret=True)
            out = raw_plan(batch_mesh(nd))(imgs)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
            print(f"OK b={b} nd={nd}")

        # plan_for auto-meshes over all local devices
        imgs = add_gaussian_noise(synthetic_batch(5, h, w, seed=0), 30.0, seed=9)
        auto = plan_for(cfg, h, w, backend="fused", batch_tile=4,
                        quantize_output=False, interpret=True)
        assert auto.mesh_size == 8
        np.testing.assert_array_equal(
            np.asarray(auto(imgs)),
            np.asarray(bg_fused(imgs, cfg, interpret=True)))
        print("OK auto-mesh")

        # sharded + double-buffered input stream composition
        np.testing.assert_array_equal(
            np.asarray(raw_plan(batch_mesh(4), "fused_streamed")(imgs)),
            np.asarray(bg_fused(imgs, cfg, interpret=True)))
        print("OK sharded+stream_input")
        """
    )


def test_single_device_fallback_is_plain_call():
    """On a 1-device host plan_for builds no mesh, and a size-1 mesh
    normalizes away: both run the unsharded kernel."""
    run_sub(
        """
        import jax, numpy as np
        from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
        from repro.kernels import bg_fused
        from repro.plan import BGPlan, plan_for
        from repro.sharding.bg_shard import batch_mesh

        assert jax.device_count() == 1
        cfg = BGConfig(r=7, sigma_s=4.0, sigma_r=50.0)
        imgs = add_gaussian_noise(synthetic_batch(3, 41, 60, seed=2), 30.0, seed=3)
        auto = plan_for(cfg, 41, 60, backend="fused", batch_tile=4,
                        quantize_output=False, interpret=True)
        one = BGPlan(cfg=cfg, mesh=batch_mesh(1), interpret=True,
                     quantize_output=False)
        assert auto.mesh is None and one.mesh is None
        for plan in (auto, one):
            np.testing.assert_array_equal(
                np.asarray(plan(imgs)),
                np.asarray(bg_fused(imgs, cfg, interpret=True)))
            # single (h, w) frame squeeze path
            np.testing.assert_array_equal(
                np.asarray(plan(imgs[0])),
                np.asarray(bg_fused(imgs[0], cfg, interpret=True)))
        print("OK fallback")
        """,
        devices=1,
    )


def test_frame_engine_micro_batches_mesh_divisible():
    """The frame engine on an 8-device plan: 11 ragged frames, every answer
    bitwise the single-device plan's, and each micro-batch padded to a
    device multiple with its pad rows counted in ``mesh_pad_rows``."""
    run_sub(
        """
        import jax, numpy as np
        from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
        from repro.plan import BGPlan
        from repro.serving import AsyncFrameEngine
        from repro.sharding.bg_shard import batch_mesh, row_pad

        assert jax.device_count() == 8
        cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
        frames = np.asarray(add_gaussian_noise(
            synthetic_batch(11, 40, 48, seed=4), 30.0, seed=5))
        ref = np.asarray(BGPlan(cfg=cfg, interpret=True)(frames))

        plan = BGPlan(cfg=cfg, mesh=batch_mesh(8), interpret=True)
        with AsyncFrameEngine(plan=plan, max_batch=8,
                              batch_window_ms=200.0) as eng:
            futs = [eng.submit(frames[i]) for i in range(11)]
            outs = [np.asarray(f.result(timeout=300)) for f in futs]
            recs = eng.timeline()
            st = eng.stats()
        for i in range(11):
            np.testing.assert_array_equal(outs[i], ref[i])
        sizes = {r.dispatch: r.n for r in recs}
        assert sum(sizes.values()) == 11 and max(sizes.values()) <= 8
        pads = sum(row_pad(8, n) for n in sizes.values())
        assert pads > 0  # 11 frames cannot all ride device multiples
        assert st.mesh_pad_rows == pads
        print("OK frame engine")
        """
    )


def test_sharded_dispatch_through_pipeline_and_streaming():
    """Fused and streaming plans on a mesh agree with their single-device
    plans on a multi-device host."""
    run_sub(
        """
        import jax, numpy as np
        from repro.core import BGConfig, add_gaussian_noise, synthetic_batch
        from repro.plan import BGPlan
        from repro.sharding.bg_shard import batch_mesh

        assert jax.device_count() == 8
        cfg = BGConfig(r=6, sigma_s=4.0, sigma_r=60.0)
        imgs = add_gaussian_noise(synthetic_batch(5, 40, 55, seed=6), 30.0, seed=7)

        np.testing.assert_array_equal(
            np.asarray(BGPlan(cfg=cfg, mesh=batch_mesh())(imgs)),
            np.asarray(BGPlan(cfg=cfg)(imgs)))

        out = BGPlan(cfg=cfg, backend="streaming", mesh=batch_mesh(4))(imgs)
        ref = BGPlan(cfg=cfg, backend="streaming")(imgs)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5)
        print("OK pipeline+streaming sharded")
        """
    )
