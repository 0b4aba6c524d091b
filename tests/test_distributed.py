"""Distributed-mechanics tests. Each test runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest process
keeps its single-device view (per the harness contract)."""
import os
import subprocess
import sys
import textwrap

import pytest

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


def test_sharded_train_step_runs_and_matches_single_device():
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_smoke_config
        from repro.launch.dryrun import _shard_tree
        from repro.models import init_params, param_logical_axes
        from repro.sharding.partitioning import DEFAULT_RULES, axis_rules
        from repro.train import OptConfig, make_train_step
        from repro.train.train_step import init_train_state
        from repro.data import lm_batches

        cfg = get_smoke_config("yi-6b")
        opt = OptConfig(lr=1e-3, warmup_steps=1, decay_steps=10)
        params = init_params(jax.random.PRNGKey(0), cfg)
        state = init_train_state(params)
        batch = next(lm_batches(cfg.vocab_size, 8, 16, 1, seed=0))
        batch = {k: jnp.asarray(v) for k, v in batch.items()}

        # single-device reference
        p_ref, _, m_ref = jax.jit(make_train_step(cfg, opt))(params, state, batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        p_sh = _shard_tree(param_logical_axes(cfg), mesh, DEFAULT_RULES,
                           jax.eval_shape(lambda: params))
        with axis_rules(DEFAULT_RULES), jax.set_mesh(mesh):
            params_d = jax.tree.map(lambda x, s: jax.device_put(x, s), params, p_sh)
            state_d = {"m": jax.tree.map(lambda x, s: jax.device_put(x, s), state["m"], p_sh),
                       "v": jax.tree.map(lambda x, s: jax.device_put(x, s), state["v"], p_sh),
                       "step": state["step"]}
            step = jax.jit(make_train_step(cfg, opt),
                           in_shardings=(p_sh, {"m": p_sh, "v": p_sh, "step": None}, None),
                           out_shardings=(p_sh, {"m": p_sh, "v": p_sh, "step": None}, None))
            p_new, _, m = step(params_d, state_d, batch)
        assert abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-3, (m, m_ref)
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_new)))
        assert d < 5e-3, d
        print("OK sharded==single", d)
        """
    )


def test_gpipe_matches_sequential():
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.pipeline_parallel import gpipe

        P_STAGES, N_MICRO, MB, D = 4, 8, 2, 16
        mesh = jax.make_mesh((P_STAGES,), ("pipe",), axis_types=(jax.sharding.AxisType.Auto,))
        ks = jax.random.split(jax.random.PRNGKey(0), P_STAGES)
        ws = jnp.stack([jax.random.normal(k, (D, D)) / jnp.sqrt(D) for k in ks])
        xs = jax.random.normal(jax.random.PRNGKey(1), (N_MICRO, MB, D))

        def stage(w, x):
            return jnp.tanh(x @ w)

        out = gpipe(stage, ws, xs, mesh, axis="pipe")
        ref = xs
        for i in range(P_STAGES):
            ref = jnp.tanh(ref @ ws[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        print("OK gpipe")
        """
    )


def test_compressed_grad_allreduce_close_to_exact():
    run_sub(
        """
        import jax, jax.numpy as jnp
        from repro.train.grad_compress import compressed_mean_grads

        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32))

        from jax.sharding import NamedSharding, PartitionSpec as P
        gd = jax.device_put(g, NamedSharding(mesh, P("data")))
        out = compressed_mean_grads({"w": gd}, mesh, dp_axes=("data",))["w"]
        exact = jnp.broadcast_to(jnp.mean(g, axis=0, keepdims=True), g.shape)
        rel = float(jnp.max(jnp.abs(out - exact)) / jnp.max(jnp.abs(exact)))
        assert rel < 0.02, rel
        print("OK compress", rel)
        """
    )


def test_mini_dryrun_cell_with_roofline():
    """End-to-end dry-run machinery on a small mesh + smoke config: lower,
    compile, memory/cost analysis, trip-count-corrected roofline terms."""
    run_sub(
        """
        import jax, json
        import dataclasses
        from repro.configs.registry import get_smoke_config
        from repro.launch.dryrun import _shard_tree
        from repro.launch.hlo_analysis import roofline_terms
        from repro.models import init_params, param_logical_axes
        from repro.sharding.partitioning import DEFAULT_RULES, axis_rules
        from repro.train import OptConfig, make_train_step
        from repro.train.optimizer import adamw_init

        cfg = dataclasses.replace(get_smoke_config("gemma2-9b"), grad_accum=2)
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pshape = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
        p_sh = _shard_tree(param_logical_axes(cfg), mesh, DEFAULT_RULES, pshape)
        oshape = jax.eval_shape(adamw_init, pshape)
        o_sh = {"m": p_sh, "v": p_sh, "step": None}
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 32), jax.numpy.int32),
            "labels": jax.ShapeDtypeStruct((8, 32), jax.numpy.int32),
        }
        with axis_rules(DEFAULT_RULES), jax.set_mesh(mesh):
            lowered = jax.jit(make_train_step(cfg, OptConfig()),
                              in_shardings=(p_sh, o_sh, None)).lower(pshape, oshape, batch)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        roof = roofline_terms(compiled.cost_analysis(), compiled.as_text())
        assert roof.flops_per_chip > 0
        assert roof.hbm_bytes_per_chip > 0
        # accum scan x layer scan must be trip-count multiplied: raw cost
        # analysis undercounts vs the structural model
        raw = compiled.cost_analysis().get("flops", 0.0)
        assert roof.flops_per_chip > 1.5 * raw, (roof.flops_per_chip, raw)
        print("OK dryrun", roof.dominant)
        """
    )


def test_elastic_restore_across_meshes():
    run_sub(
        """
        import tempfile, jax, jax.numpy as jnp
        from repro.checkpoint import CheckpointManager
        from repro.checkpoint.elastic import elastic_restore, train_state_shardings
        from repro.configs.registry import get_smoke_config
        from repro.models import init_params
        from repro.train.optimizer import adamw_init

        cfg = get_smoke_config("stablelm-1.6b")
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(3, {"params": params, "opt": opt}, {"step": 3})
            # restore onto a DIFFERENT topology (4x2 vs training's 1 device)
            mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
            p2, o2, meta = elastic_restore(mgr, cfg, mesh)
            assert meta["step"] == 3
            ok = jax.tree.map(lambda a, b: bool(jnp.allclose(a, jnp.asarray(b))), params, p2)
            assert all(jax.tree.leaves(ok))
            # leaves actually live on the new mesh
            leaf = jax.tree.leaves(p2)[0]
            assert len(leaf.devices()) > 1 or leaf.sharding.num_devices == 8
        print("OK elastic")
        """
    )


def test_ring_attention_matches_plain():
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import AttnSpec
        from repro.models.attention import _sdpa_plain
        from repro.sharding.ring_attention import ring_attention

        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        B, S, H, KV, D = 2, 64, 4, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        for spec, cap in [
            (AttnSpec(kind="global"), 0.0),
            (AttnSpec(kind="global", causal=False), 0.0),
            (AttnSpec(kind="local", window=24), 0.0),
            (AttnSpec(kind="global"), 30.0),
        ]:
            ref = _sdpa_plain(q, k, v, pos, pos, spec, cap)
            out = ring_attention(q, k, v, pos, spec, mesh, axis="data", softcap=cap)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
        print("OK ring attention")
        """
    )
