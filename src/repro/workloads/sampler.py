"""On-device op-stream sampler: ``jax.random`` end to end.

The zipfian is a bounded inverse-CDF sampler over ranks ``[0, N)`` --
unlike ``numpy.random.zipf`` there is no unbounded tail to fold back
onto the key space, so no modulo-aliasing bias (the old host
generator's ``(rng.zipf(a) - 1) % N`` inflated hot keys with the
wrapped tail).  Ranks are scrambled into keys with a Knuth
multiplicative hash so popularity is not correlated with key order;
``hot_offset`` rotates ranks before scrambling, which moves the ENTIRE
hot set to different keys -- the hot-set-shift churn knob.

``repro.workloads.reference`` mirrors this math in numpy for
distribution tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import engine
from repro.workloads.spec import (HASHED, LATEST, SEQ, UNIFORM, ZIPF,
                                  GenState, WorkloadSpec)

SCRAMBLE_MUL = 2654435761       # Knuth multiplicative constant


def zipf_ranks(u: jax.Array, n: int, theta: jax.Array) -> jax.Array:
    """Bounded inverse-CDF zipfian ranks in ``[0, n)`` from uniforms ``u``.

    P(rank = r) = ((r+2)^(1-t) - (r+1)^(1-t)) / (n^(1-t) - 1); theta is
    clamped away from the removable singularity at 1.
    """
    t = jnp.maximum(theta, 1e-3)
    t = jnp.where(jnp.abs(t - 1.0) < 1e-4, t + 2e-4, t)
    c = jnp.power(jnp.float32(n), 1.0 - t)
    ranks = jnp.power((c - 1.0) * u + 1.0, 1.0 / (1.0 - t)) - 1.0
    return jnp.clip(ranks, 0, n - 1).astype(jnp.int32)


def scramble(ranks: jax.Array, offset: jax.Array, key_space: int
             ) -> jax.Array:
    """Rank -> key via multiplicative scrambling (uint32 wraparound)."""
    x = (ranks + offset).astype(jnp.uint32) * jnp.uint32(SCRAMBLE_MUL)
    return (x % jnp.uint32(key_space)).astype(jnp.int32)


def sample_keys(key: jax.Array, dist: jax.Array, theta: jax.Array,
                hot_offset: jax.Array, ptr: jax.Array, batch: int,
                key_space: int) -> tuple[jax.Array, jax.Array]:
    """One batch of keys under a (traced) distribution code.

    Returns ``(keys, ptr')``; the insert pointer advances only when the
    SEQ or HASHED distribution was selected.
    """
    ku, kz = jax.random.split(key)
    uni = jax.random.randint(ku, (batch,), 0, key_space, jnp.int32)
    u = jax.random.uniform(kz, (batch,))
    ranks = zipf_ranks(u, key_space, theta)
    zipf = scramble(ranks, hot_offset, key_space)
    latest = jnp.mod(ptr - 1 - ranks, key_space).astype(jnp.int32)
    seq = jnp.mod(ptr + jnp.arange(batch, dtype=jnp.int32),
                  key_space).astype(jnp.int32)
    hashed = scramble(seq, jnp.int32(0), key_space)
    keys = jnp.select([dist == UNIFORM, dist == ZIPF, dist == LATEST,
                       dist == HASHED], [uni, zipf, latest, hashed], seq)
    ptr = jnp.where((dist == SEQ) | (dist == HASHED), ptr + batch, ptr)
    return keys, ptr


def sample_batch(key: jax.Array, sp: WorkloadSpec, gst: GenState, *,
                 batch: int, key_space: int, value_width: int
                 ) -> tuple[GenState, engine.OpBatch]:
    """One ``OpBatch`` drawn from the spec (op kind + keys + scan lens)."""
    kop, kkey, klen = jax.random.split(key, 3)
    u = jax.random.uniform(kop, ())
    cg = sp.p_get
    cp = cg + sp.p_put
    cd = cp + sp.p_del
    kind = jnp.where(
        u < cg, engine.GET,
        jnp.where(u < cp, engine.PUT,
                  jnp.where(u < cd, engine.DELETE,
                            engine.SCAN))).astype(jnp.int32)
    is_write = (kind == engine.PUT) | (kind == engine.DELETE)
    dist = jnp.where(is_write, sp.wdist, sp.dist)
    theta = jnp.where(is_write, sp.wtheta, sp.theta)
    keys, ptr = sample_keys(kkey, dist, theta, sp.hot_offset, gst.ptr,
                            batch, key_space)
    lens = 1 + jax.random.randint(klen, (batch,), 0,
                                  jnp.maximum(sp.scan_len, 1), jnp.int32)
    op = engine.OpBatch(
        kind=kind, keys=keys,
        vals=jnp.broadcast_to(keys[:, None].astype(jnp.float32),
                              (batch, value_width)),
        valid=jnp.ones((batch,), bool),
        aux=jnp.where(kind == engine.SCAN, lens, 0))
    return GenState(ptr=ptr), op


def sample_ops(key: jax.Array, work, n: int, batch: int, *, key_space: int,
               value_width: int, gst: GenState | None = None,
               t0: jax.Array | int = 0
               ) -> tuple[engine.OpBatch, GenState]:
    """Stacked op stream (leading axis = n batches) for a spec or a
    ``PhaseSchedule`` -- the format ``engine.run_ops`` consumes.  Pure
    generation; ``repro.workloads.runner`` fuses generation with
    execution instead of materializing the stream."""
    from repro.workloads.schedule import as_schedule, spec_at
    sched = as_schedule(work, n)
    if gst is None:
        gst = GenState(ptr=jnp.int32(key_space // 2))

    def step(carry, t):
        g, r = carry
        r, k = jax.random.split(r)
        g, op = sample_batch(k, spec_at(sched, t), g, batch=batch,
                             key_space=key_space, value_width=value_width)
        return (g, r), op

    (gst, _), ops = lax.scan(step, (gst, key),
                             jnp.int32(t0) + jnp.arange(n, dtype=jnp.int32))
    return ops, gst
