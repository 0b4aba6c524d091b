"""Ring attention: sequence-parallel exact attention over a mesh axis.

For long-context prefill the residual stream can be sharded along the
sequence (SP_RULES); attention then needs every (q, k) pair across shards.
Ring attention keeps K/V moving around the ring with collective_permute while
each shard accumulates its queries' online-softmax state — memory per shard
is O(S_local^2-block) and the K/V transfer overlaps block compute on real
hardware (one ICI hop per step).

This is the shard_map/SP counterpart of models.attention._sdpa_blocked (same
online-softmax math, distributed axis instead of scan axis). Exactness vs the
single-device reference is asserted in tests/test_distributed.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import AttnSpec
from repro.models.attention import NEG_INF, _mask_logits

__all__ = ["ring_attention"]


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    positions: jnp.ndarray,
    spec: AttnSpec,
    mesh,
    axis: str = "data",
    softcap: float = 0.0,
):
    """q (B,S,H,D), k/v (B,S,KV,D), positions (B,S); S sharded over `axis`.

    Returns (B,S,H,D) sharded the same way. Exact (online-softmax merge).
    """
    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(q, k, v, pos):
        B, Sl, H, D = q.shape
        KV = k.shape[2]
        G = H // KV
        qg = (q * (1.0 / jnp.sqrt(D).astype(q.dtype))).reshape(B, Sl, KV, G, D)
        qpos = pos

        m = jnp.full((B, KV, G, Sl), NEG_INF, jnp.float32)
        l = jnp.zeros((B, KV, G, Sl), jnp.float32)
        acc = jnp.zeros((B, KV, G, Sl, D), jnp.float32)
        kc, vc, kpos = k, v, pos

        for _ in range(n):  # static ring walk
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc).astype(jnp.float32)
            if softcap > 0.0:
                logits = softcap * jnp.tanh(logits / softcap)
            logits = _mask_logits(
                logits, qpos[:, None, None, :], kpos[:, None, None, :], spec
            )
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32)
            )
            m = m_new
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            kpos = jax.lax.ppermute(kpos, axis, perm)

        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1).reshape(B, Sl, H, D).astype(q.dtype)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis),
        ),
        out_specs=P(None, axis, None, None),
    )
    return fn(q, k, v, positions)
