"""Logical-axis sharding rules (MaxText-style) resolved to NamedSharding.

Models annotate activations/params with *logical* axis names; a rules table
maps those to physical mesh axes. GSPMD handles non-divisible dimensions by
internal padding, which is why plain pjit + constraints (not shard_map) is the
primary distribution mechanism (e.g. llama4's 40 heads over 16-way TP).

Usage:
    with axis_rules(DEFAULT_RULES), mesh:
        y = logical_constraint(x, "batch", "seq", "embed")
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES",
    "SP_RULES",
    "axis_rules",
    "current_rules",
    "logical_constraint",
    "logical_constraint_padded",
    "logical_spec",
    "param_sharding",
    "get_abstract_mesh",
]

# logical axis -> physical mesh axis (or tuple of axes, or None = replicate)
DEFAULT_RULES: dict[str, object] = {
    # activations
    "batch": ("pod", "data"),  # data parallel
    "seq": None,  # sequence replicated (see SP_RULES)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "qkv": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",  # EP: experts over the TP axis
    "expert_mlp": "model",  # TP-mode MoE: expert hidden dim over TP axis
    "kv_len": None,
    "kv_dim": "model",  # fallback TP axis for KV caches when heads don't divide
    # params
    "fsdp": ("pod", "data"),  # ZeRO-3 axis for the non-TP param dim
    "conv_k": None,
    "rnn": "model",
    "stack": None,  # scan-stacked layer axis
}

# Megatron-style sequence parallelism for the residual stream: long-context
# prefill shards activations along seq instead of replicating them.
SP_RULES = dict(DEFAULT_RULES, seq=("pod", "data"), batch=None)

_state = threading.local()


def current_rules() -> Optional[dict]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def axis_rules(rules: dict):
    prev = current_rules()
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def _mesh_axes_in_use() -> set:
    mesh = get_abstract_mesh()
    if mesh is None:
        return set()
    return set(mesh.axis_names)


def get_abstract_mesh() -> Optional[Mesh]:
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return None
    return m


def logical_spec(
    names: Sequence[Optional[str]],
    rules: Optional[dict] = None,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """Resolve logical axis names to a PartitionSpec under current rules/mesh.

    With `shape`, axes whose dimension does not divide the mesh axis are
    dropped (GSPMD would otherwise pad — e.g. 4 kv heads forced onto a 16-way
    axis quadruples the tensor and injects resharding collectives)."""
    rules = rules or current_rules() or {}
    mesh = get_abstract_mesh()
    avail = _mesh_axes_in_use()
    used: set = set()

    def size_of(axis_name):
        return mesh.shape[axis_name] if mesh is not None else 1

    def resolve(i, name):
        if name is None:
            return None
        phys = rules.get(name)
        if phys is None:
            return None
        cand = [phys] if isinstance(phys, str) else list(phys)
        cand = [a for a in cand if a in avail and a not in used]
        if shape is not None:
            dim = shape[i]
            picked = []
            for a in cand:
                if dim % size_of(a) == 0:
                    picked.append(a)
                    dim //= size_of(a)
            cand = picked
        if not cand:
            return None
        used.update(cand)
        if isinstance(phys, str):
            return cand[0]
        return tuple(cand)

    resolved = [resolve(i, n) for i, n in enumerate(names)]
    # drop trailing Nones for a tidy spec
    while resolved and resolved[-1] is None:
        resolved.pop()
    return P(*resolved)


def logical_constraint(x, *names):
    """with_sharding_constraint by logical names; no-op without mesh/rules.
    Divisibility-aware: never asks GSPMD to pad a dimension."""
    if current_rules() is None or get_abstract_mesh() is None:
        return x
    spec = logical_spec(names, shape=x.shape)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def logical_constraint_padded(x, *names):
    """Like logical_constraint but WITHOUT the divisibility check: GSPMD pads
    the dimension internally. Use where padding waste beats the alternative —
    e.g. attention queries with 40 heads on 16-way TP: padded head sharding
    costs 20% replicated compute, while unsharded heads force GSPMD into
    head_dim contractions that all-reduce the S^2 logits per block."""
    if current_rules() is None or get_abstract_mesh() is None:
        return x
    spec = logical_spec(names)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def param_sharding(
    logical_axes, mesh: Mesh, rules: Optional[dict] = None, shape=None
):
    """NamedSharding for a parameter annotated with logical axes.

    With `shape`, any assignment whose dimension is not divisible by the mesh
    axes it would claim is dropped (replicated) WITHOUT consuming the mesh
    axis — so a later logical axis can claim it instead. This is how e.g. a
    KV cache annotated (batch, kv_len, kv_heads, kv_dim) lands on head-dim TP
    when kv_heads (8) doesn't divide the 16-way model axis: jit-boundary
    shardings must tile exactly, unlike internal constraints.
    """
    rules = rules or DEFAULT_RULES
    avail = set(mesh.axis_names)
    used = set()

    def resolve(i, name):
        if name is None:
            return None
        phys = rules.get(name)
        if phys is None:
            return None
        cand = [phys] if isinstance(phys, str) else list(phys)
        cand = [a for a in cand if a in avail and a not in used]
        if not cand:
            return None
        if shape is not None:
            dim = shape[i]
            picked = []
            for a in cand:
                n = mesh.shape[a]
                if dim % n == 0 and dim // n >= 1:
                    picked.append(a)
                    dim //= n
            cand = picked
        if not cand:
            return None
        used.update(cand)
        if isinstance(phys, str):
            return cand[0]
        return tuple(cand)

    return NamedSharding(mesh, P(*[resolve(i, n) for i, n in enumerate(logical_axes)]))
