"""GPipe-style pipeline parallelism over a dedicated mesh axis.

shard_map + ppermute implementation: each device along the `pipe` axis holds
one stage's params; microbatches stream through with the classic
(n_micro + n_stages - 1)-step schedule. Bubble fraction = (P-1)/(m+P-1).

At production scale this composes with the (pod, data, model) mesh by mapping
`pod` (or a factor of `data`) to `pipe` — the multi-pod dry-run keeps pod as
pure DP (the default); this module is the PP building block, exercised on
host-device meshes in tests/test_distributed.py.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["gpipe"]


def gpipe(
    stage_fn: Callable,
    stage_params,
    microbatches: jnp.ndarray,
    mesh,
    axis: str = "pipe",
):
    """Run `y = stage_{P-1}(...stage_0(x))` over microbatches, pipelined.

    stage_fn(params_one_stage, x) -> y, same shape as x.
    stage_params: pytree with a leading stage axis of size P = mesh.shape[axis].
    microbatches: (n_micro, mb, ...) array (replicated input).
    Returns (n_micro, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params, xs):
        # params: leading axis 1 (this stage) -> squeeze
        params = jax.tree.map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        total = n_micro + n_stages - 1
        # initial carries must be marked varying over the pipe axis (vma typing)
        mark = lambda t: jax.lax.pcast(t, (axis,), to="varying")
        buf = mark(jnp.zeros_like(xs[0]))
        outs = mark(jnp.zeros_like(xs))

        def body(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t while it exists
            feed = xs[jnp.minimum(t, n_micro - 1)]
            inp = jnp.where(idx == 0, feed, buf)
            y = stage_fn(params, inp)
            # the last stage finishes microbatch t-(P-1)
            done = t - (n_stages - 1)
            write = jnp.logical_and(idx == n_stages - 1, done >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.maximum(done, 0), 0
            )
            outs = jnp.where(write, upd, outs)
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, total, body, (buf, outs))
        # broadcast the last stage's outputs to every stage
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
    )
    return fn(stage_params, microbatches)
