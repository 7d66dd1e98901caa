"""WorkloadSpec: a jit-/vmap-/scan-safe description of an op mix.

Every field is a traced JAX scalar so specs can be stacked on a leading
axis (a ``PhaseSchedule``) and selected per scan step with a dynamic
index -- the whole schedule then runs under ONE ``lax.scan`` dispatch,
and stacks vmap across tenants.  Static knobs (batch size, key space)
stay outside the spec, on the call.

Op mix is batch-granular, like the paper's YCSB driver: each generated
batch is entirely one op kind, drawn from ``(p_get, p_put, p_del,
p_scan)``.  Key distributions (read side and write side independently,
Twitter-cluster style):

  UNIFORM   uniform over ``[0, key_space)``
  ZIPF      bounded inverse-CDF zipfian over ranks, multiplicative rank
            scrambling (+ ``hot_offset`` rotates WHICH keys are hot)
  LATEST    zipfian over recency behind the insert pointer (YCSB-D reads)
  SEQ       sequential inserts at the pointer (YCSB-D/E writes); the
            pointer lives in ``GenState`` and advances on use
  HASHED    the SEQ pointer's keys, scrambled (a multiplicative
            permutation of the key space when ``key_space`` is a power of
            two): a load in scrambled order, as YCSB's default
            ``insertorder=hashed`` loads, though not by YCSB's hash.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

UNIFORM, ZIPF, LATEST, SEQ, HASHED = 0, 1, 2, 3, 4

_DIST = {"uniform": UNIFORM, "zipf": ZIPF, "latest": LATEST, "seq": SEQ,
         "hashed": HASHED}


class WorkloadSpec(NamedTuple):
    """Op mix + key-distribution parameters; all leaves traced scalars."""
    p_get: jax.Array        # f32: P(batch is point reads)
    p_put: jax.Array        # f32: P(batch is writes)
    p_del: jax.Array        # f32: P(batch is deletes)
    p_scan: jax.Array       # f32: P(batch is range scans)
    dist: jax.Array         # i32: read/scan-start key distribution
    theta: jax.Array        # f32: zipf exponent for ``dist``
    wdist: jax.Array        # i32: put/delete key distribution
    wtheta: jax.Array       # f32: zipf exponent for ``wdist``
    hot_offset: jax.Array   # i32: rank-scramble rotation (hot-set shift)
    scan_len: jax.Array     # i32: max keys per scan lane


class GenState(NamedTuple):
    """Mutable generator state threaded through sampling: the insert
    pointer for LATEST reads / SEQ and HASHED writes."""
    ptr: jax.Array          # i32


def init_gen(key_space: int) -> GenState:
    return GenState(ptr=jnp.int32(key_space // 2))


def spec(*, read: float = 0.5, delete: float = 0.0, scan: float = 0.0,
         put: float | None = None, dist: str = "zipf", theta: float = 0.99,
         wdist: str | None = None, wtheta: float | None = None,
         hot_offset: int = 0, scan_len: int = 16) -> WorkloadSpec:
    """Build a WorkloadSpec from python knobs.  ``put`` defaults to the
    remaining probability mass; write distribution defaults to the read
    one (``"latest"`` reads default to ``"seq"`` writes, YCSB-D style)."""
    if put is None:
        put = 1.0 - read - delete - scan
    assert put >= -1e-6, (read, delete, scan)
    if dist == "zipf" and theta == 0.0:
        dist = "uniform"                     # theta=0 degenerates to uniform
    if wdist is None:
        wdist = "seq" if dist == "latest" else dist
    if wtheta is None:
        wtheta = theta
    if wdist == "zipf" and wtheta == 0.0:
        wdist = "uniform"
    return WorkloadSpec(
        p_get=jnp.float32(read), p_put=jnp.float32(max(put, 0.0)),
        p_del=jnp.float32(delete), p_scan=jnp.float32(scan),
        dist=jnp.int32(_DIST[dist]), theta=jnp.float32(theta),
        wdist=jnp.int32(_DIST[wdist]), wtheta=jnp.float32(wtheta),
        hot_offset=jnp.int32(hot_offset), scan_len=jnp.int32(scan_len))
