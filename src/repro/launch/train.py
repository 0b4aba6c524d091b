"""Production training launcher.

On a real cluster:
    python -m repro.launch.train --arch yi-6b --steps 1000 \
        --ckpt-dir gs://.../ckpts --mesh 16x16

Single-process CPU (examples/tests) uses host devices. Multi-host TPU would
call jax.distributed.initialize() first (guarded below) and pass the
latency-hiding XLA flags from launch.mesh.LATENCY_HIDING_FLAGS.
"""
from __future__ import annotations

import argparse
import dataclasses
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--mesh", default=None, help="e.g. 4x2 => (data,model)")
    ap.add_argument("--distributed", action="store_true")
    args = ap.parse_args()

    if args.distributed:
        import jax

        jax.distributed.initialize()

    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config, get_smoke_config
    from repro.data import lm_batches
    from repro.launch.dryrun import _shard_tree  # shared sharding helper
    from repro.models import param_logical_axes
    from repro.sharding.partitioning import DEFAULT_RULES, axis_rules
    from repro.train import OptConfig, Trainer

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt = OptConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                    decay_steps=args.steps)
    trainer = Trainer(cfg, opt, args.ckpt_dir, ckpt_every=args.ckpt_every)
    print(f"[train] {cfg.name}: {trainer.init_or_resume()} at step {trainer.step}")

    ctx = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[: len(dims)]
        mesh = jax.make_mesh(dims, axes)
        ctx = (axis_rules(DEFAULT_RULES), jax.set_mesh(mesh))
        for c in ctx:
            c.__enter__()
        p_sh = _shard_tree(
            param_logical_axes(cfg), mesh, DEFAULT_RULES,
            jax.eval_shape(lambda: trainer.params),
        )
        trainer.params = jax.tree.map(jax.device_put, trainer.params, p_sh)
        trainer.opt_state = {
            "m": jax.tree.map(jax.device_put, trainer.opt_state["m"], p_sh),
            "v": jax.tree.map(jax.device_put, trainer.opt_state["v"], p_sh),
            "step": trainer.opt_state["step"],
        }

    def log(step, m):
        if step % 10 == 0 or step == 1:
            print(
                f"  step {step:5d} loss {m['loss']:.4f} "
                f"gnorm {m.get('grad_norm', 0):.2f} {m['step_time']*1e3:.0f}ms"
            )

    batches = (
        {k: jnp.asarray(v) for k, v in b.items()}
        for b in lm_batches(cfg.vocab_size, args.batch, args.seq, args.steps,
                            seed=trainer.step)
    )
    final = trainer.run(batches, max_steps=args.steps, log_fn=log)
    print(f"[train] done at step {trainer.step}: {final}")
    if ctx:
        for c in reversed(ctx):
            c.__exit__(None, None, None)


if __name__ == "__main__":
    main()
