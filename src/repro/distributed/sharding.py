"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Model code annotates activations/params with *logical* axis names; the
rules map them to mesh axes.  Mapping is size-aware: a mesh axis is only
applied where the dimension is divisible by it (e.g. 4 KV heads on a
16-way model axis stay replicated instead of 4x-padded).
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

# logical axis -> mesh axis (or tuple of axes, or None = replicated)
DEFAULT_RULES = {
    # activations
    "batch": ("pod", "data"),    # pod folds into DP when present
    "seq": None,
    "act_seq": "data",           # context/sequence parallelism (long ctx)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "capacity": None,
    # param-only axes
    "layers": None,
    "stack": None,
    "zero": "data",              # ZeRO-1 optimizer-state sharding
    # decode caches: prefer kv_heads on model; head_dim picks model up when
    # kv_heads isn't divisible (size-aware mapping drops it there)
    "cache_seq": None,
    "cache_head_dim": "model",
    # paged kv pools
    "pages": "data",
    "page_tokens": None,
    # shared-nothing PartitionedDB shards: the leading partition axis of
    # every EngineState leaf maps onto the "part" mesh axis (size-aware:
    # P partitions shard over D devices only when D divides P)
    "part": "part",
}

_state = threading.local()


def current_rules() -> dict:
    return getattr(_state, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(rules: dict):
    prev = getattr(_state, "rules", DEFAULT_RULES)
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def logical_to_spec(logical, mesh, shape=None, allowed=None) -> P:
    """Map logical axis names to a PartitionSpec for `mesh`.

    Drops mesh axes that don't exist, that aren't in ``allowed`` (e.g.
    Manual axes inside shard_map), and (when `shape` is given) axes that
    don't divide the dimension.
    """
    rules = current_rules()
    have = set(mesh.axis_names) if mesh is not None else set()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes)) if have else {}
    if allowed is not None:
        have &= set(allowed)
    out = []
    used = set()
    for i, name in enumerate(logical):
        mapped = rules.get(name) if name is not None else None
        if mapped is None:
            out.append(None)
            continue
        cands = mapped if isinstance(mapped, tuple) else (mapped,)
        cands = [c for c in cands if c in have and c not in used]
        if shape is not None:
            keep, prod = [], 1
            for c in cands:
                sz = sizes[c]
                if shape[i] % (prod * sz) == 0:
                    keep.append(c)
                    prod *= sz
            cands = keep
        if not cands:
            out.append(None)
        elif len(cands) == 1:
            out.append(cands[0])
            used.add(cands[0])
        else:
            out.append(tuple(cands))
            used.update(cands)
    return P(*out)


def constrain(x, logical):
    """with_sharding_constraint under the ambient (abstract) mesh; no-op
    when tracing without a mesh (CPU tests).  Manual axes (inside
    shard_map) are excluded -- only Auto axes may be constrained."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    allowed = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
               if t == jax.sharding.AxisType.Auto}
    if not allowed:
        return x
    spec = logical_to_spec(logical, mesh, shape=x.shape, allowed=allowed)
    return jax.lax.with_sharding_constraint(x, spec)


def spec_tree(specs, shapes, mesh):
    """specs: pytree of logical tuples; shapes: matching pytree of shaped
    values -> pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda s, v: logical_to_spec(s, mesh, shape=v.shape), specs, shapes,
        is_leaf=lambda s: isinstance(s, tuple) and all(
            isinstance(e, (str, type(None))) for e in s))


def named_sharding_tree(specs, shapes, mesh):
    return jax.tree.map(
        lambda sp: jax.sharding.NamedSharding(mesh, sp),
        spec_tree(specs, shapes, mesh),
        is_leaf=lambda s: isinstance(s, P))


def leading_axis_sharding(tree, mesh, logical: str = "part"):
    """NamedShardings that shard every leaf's LEADING axis by ``logical``
    (rest replicated) -- the layout of a stacked per-partition
    ``EngineState`` over the partition mesh.  Size-aware via the same
    rules as everything else: a leaf whose leading dim the mesh axis
    does not divide stays replicated rather than padded."""
    def one(x):
        spec = logical_to_spec((logical,) + (None,) * (x.ndim - 1), mesh,
                               shape=x.shape)
        return jax.sharding.NamedSharding(mesh, spec)
    return jax.tree.map(one, tree)
