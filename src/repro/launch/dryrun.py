import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below this line may import jax -----------------------------
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import SHAPES  # noqa: E402
from repro.configs.registry import ARCHS, cell_skip_reason, get_config  # noqa: E402
from repro.launch.hlo_analysis import roofline_terms  # noqa: E402
from repro.launch.mesh import dp_size, make_production_mesh  # noqa: E402
from repro.launch.specs import batch_logical_axes, input_specs, shape_cfg  # noqa: E402
from repro.models import (  # noqa: E402
    forward,
    init_params,
    model_flops_per_token,
    param_logical_axes,
)
from repro.sharding.partitioning import (  # noqa: E402
    DEFAULT_RULES,
    axis_rules,
    param_sharding,
)
from repro.train.optimizer import OptConfig  # noqa: E402
from repro.train.train_step import make_train_step  # noqa: E402

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

This proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM, and unsupported collectives all fail here.
Results (memory_analysis, cost_analysis, collective schedule, roofline terms)
are written one JSON per cell for EXPERIMENTS.md §Dry-run / §Roofline.
"""


def _is_axes(x):
    return isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)


def _shard_tree(axes_tree, mesh, rules, shapes_tree=None):
    if shapes_tree is None:
        return jax.tree.map(
            lambda axes: param_sharding(axes, mesh, rules),
            axes_tree,
            is_leaf=_is_axes,
        )
    flat_axes, treedef = jax.tree.flatten(axes_tree, is_leaf=_is_axes)
    flat_shapes = treedef.flatten_up_to(shapes_tree)
    out = [
        param_sharding(a, mesh, rules, shape=tuple(s.shape))
        for a, s in zip(flat_axes, flat_shapes)
    ]
    return jax.tree.unflatten(treedef, out)


def _make_decode_fn(cfg):
    def serve_step(params, caches, tokens, positions, cross_ctx=None):
        logits, new_caches, _ = forward(
            params, cfg, tokens=tokens, positions=positions[:, None],
            mode="decode", caches=caches, cross_ctx=cross_ctx,
        )
        return logits[:, 0], new_caches

    return serve_step


def _make_prefill_fn(cfg):
    def prefill(params, batch):
        kw = {k: v for k, v in batch.items() if k in ("tokens", "embeds", "cross_ctx")}
        if cfg.encoder_only:
            logits, _, _ = forward(params, cfg, mode="train", **kw)
            return logits
        from repro.models import init_caches

        ref = batch["tokens"] if "tokens" in batch else batch["embeds"]
        B, S = ref.shape[0], ref.shape[1]
        caches = init_caches(cfg, B, S)
        logits, new_caches, _ = forward(
            params, cfg, mode="prefill", caches=caches, **kw
        )
        return logits, new_caches

    return prefill


def run_cell(arch: str, shape_name: str, multi_pod: bool, rules=None) -> dict:
    """Lower + compile one cell; return the §Dry-run/§Roofline record."""
    rules = rules or DEFAULT_RULES
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "ok",
    }
    skip = cell_skip_reason(arch, shape_name)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    t0 = time.monotonic()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    dp = dp_size(mesh)
    cfg = shape_cfg(get_config(arch), shape, dp)

    params_shape = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    p_axes = param_logical_axes(cfg)
    p_sh = _shard_tree(p_axes, mesh, rules, params_shape)
    batch = input_specs(cfg, shape)
    b_axes = batch_logical_axes(cfg, shape)
    b_sh = _shard_tree(b_axes, mesh, rules, batch)

    with axis_rules(rules), jax.set_mesh(mesh):
        if shape.kind == "train":
            from repro.train.optimizer import adamw_init

            opt_shape = jax.eval_shape(adamw_init, params_shape)
            opt_sh = {"m": p_sh, "v": p_sh, "step": param_sharding((), mesh, rules)}
            fn = make_train_step(cfg, OptConfig())
            lowered = jax.jit(
                fn,
                in_shardings=(p_sh, opt_sh, b_sh),
                out_shardings=(p_sh, opt_sh, None),
                donate_argnums=(0, 1),
            ).lower(params_shape, opt_shape, batch)
        elif shape.kind == "prefill":
            fn = _make_prefill_fn(cfg)
            out_sh = None
            if not cfg.encoder_only:
                from repro.models import cache_logical_axes, init_caches

                cache_shape = jax.eval_shape(
                    lambda: init_caches(cfg, shape.global_batch, shape.seq_len)
                )
                cache_ax = cache_logical_axes(cfg, shape.global_batch, shape.seq_len)
                out_sh = (None, _shard_tree(cache_ax, mesh, rules, cache_shape))
            lowered = jax.jit(fn, in_shardings=(p_sh, b_sh), out_shardings=out_sh).lower(
                params_shape, batch
            )
        else:  # decode
            fn = _make_decode_fn(cfg)
            cache_sh = b_sh.pop("caches")
            cache_shape = batch.pop("caches")
            args_sh = [p_sh, cache_sh, b_sh["tokens"], b_sh["positions"]]
            args = [params_shape, cache_shape, batch["tokens"], batch["positions"]]
            if cfg.frontend == "vision":
                args_sh.append(b_sh["cross_ctx"])
                args.append(batch["cross_ctx"])
            lowered = jax.jit(
                fn,
                in_shardings=tuple(args_sh),
                donate_argnums=(1,),
            ).lower(*args)

        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    roof = roofline_terms(cost, hlo)

    # MODEL_FLOPS: 6*N_active*D train / 2*N_active*D forward per step
    tokens_per_step = (
        shape.global_batch * shape.seq_len
        if shape.kind in ("train", "prefill")
        else shape.global_batch
    )
    mf = model_flops_per_token(cfg, train=(shape.kind == "train")) * tokens_per_step
    hlo_flops_total = roof.flops_per_chip * chips
    rec.update(
        {
            "chips": chips,
            "grad_accum": cfg.grad_accum,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                k: getattr(mem, k)
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            },
            "cost": {k: cost.get(k) for k in ("flops", "bytes accessed") if k in cost},
            "roofline": roof.as_dict(),
            "model_flops": mf,
            "useful_flops_ratio": mf / hlo_flops_total if hlo_flops_total else None,
            "tokens_per_step": tokens_per_step,
        }
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id, or omit for all")
    ap.add_argument("--shape", default=None, choices=list(SHAPES), help="one shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true", help="recompute existing cells")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip existing] {tag}")
                    continue
                print(f"[cell] {tag} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mp)
                except Exception as e:  # a failing cell is a bug in the system
                    failures += 1
                    rec = {
                        "arch": arch,
                        "shape": shape,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2, default=str)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (
                        f" dom={r['dominant']} comp={r['compute_s']:.3f}s"
                        f" mem={r['memory_s']:.3f}s coll={r['collective_s']:.3f}s"
                        f" compile={rec['compile_s']}s"
                    )
                print(f"[done] {tag}: {status}{extra}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
