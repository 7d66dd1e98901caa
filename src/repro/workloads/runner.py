"""Fused workload execution: generation + engine step under one scan.

``run_schedule`` interleaves ``sample_batch`` with ``engine.engine_step``
inside a single ``lax.scan``, so a whole workload segment -- sampling,
data ops, rate limiting, watermark compactions, the §5.3 read policy --
is ONE jitted dispatch.  ``run_tenants`` vmaps it across a stacked
EngineState (PartitionedDB shards) with per-tenant schedules for
multi-tenant mixes.

Per-step outputs are compact aggregates (``StepStats``), not the full
value tensors, so T-batch segments don't materialize T*B*V floats.

The whole ``EngineState`` is the scan carry, so the preemptible
compaction carry (``EngineState.comp``, ``cfg.compaction_quantum > 0``)
threads through segments for free: a job triggered in one batch drains
across the following batches of the same dispatch -- and across
successive ``run_workload`` calls, since the facade feeds the returned
state back in.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import engine
from repro.workloads.sampler import sample_batch
from repro.workloads.schedule import PhaseSchedule, spec_at
from repro.workloads.spec import GenState


class StepStats(NamedTuple):
    """Per-batch aggregates stacked over the segment."""
    kind: jax.Array         # i32[T]: op kind executed
    found: jax.Array        # i32[T]: found lanes (get) / non-empty scans
    fast: jax.Array         # i32[T]: lanes served from the fast tier
    returned: jax.Array     # i32[T]: scan keys returned


def run_schedule(estate: engine.EngineState, gst: GenState, rng: jax.Array,
                 sched: PhaseSchedule, cfg: engine.EngineConfig, *,
                 n_batches: int, batch: int,
                 t0: jax.Array | int = 0
                 ) -> tuple[engine.EngineState, GenState, jax.Array,
                            StepStats]:
    """Run ``n_batches`` schedule steps starting at step index ``t0``.

    ``t0`` lets a caller split one schedule across dispatches (warmup /
    measurement) while staying on the same phase timeline; ``gst`` and
    ``rng`` thread through so the stream continues exactly where the
    previous segment stopped.
    """
    ks = cfg.tier.key_space

    def step(carry, t):
        est, g, r = carry
        r, k = jax.random.split(r)
        g, op = sample_batch(k, spec_at(sched, t), g, batch=batch,
                             key_space=ks,
                             value_width=cfg.tier.value_width)
        est, res = engine.engine_step(est, op, cfg)
        st = StepStats(
            kind=op.kind,
            found=jnp.sum(res.found.astype(jnp.int32)),
            fast=jnp.sum((res.src == 0).astype(jnp.int32)
                         & (op.kind == engine.GET).astype(jnp.int32)),
            returned=jnp.where(op.kind == engine.SCAN,
                               jnp.sum(res.src), 0))
        return (est, g, r), st

    steps = jnp.int32(t0) + jnp.arange(n_batches, dtype=jnp.int32)
    (estate, gst, rng), stats = lax.scan(step, (estate, gst, rng), steps)
    return estate, gst, rng, stats


@functools.lru_cache(maxsize=256)
def jit_run_schedule(cfg: engine.EngineConfig, n_batches: int, batch: int,
                     donate: bool = True):
    """Jitted ``run_schedule`` with the engine state donated; cached per
    (config, segment shape) so facades sharing a config share compiles."""
    fn = functools.partial(run_schedule, cfg=cfg, n_batches=n_batches,
                           batch=batch)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def run_tenants(estates: engine.EngineState, gsts: GenState,
                rngs: jax.Array, scheds: PhaseSchedule,
                cfg: engine.EngineConfig, *, n_batches: int, batch: int,
                t0: jax.Array | int = 0):
    """vmap ``run_schedule`` across tenants: every input carries a leading
    tenant axis (stacked EngineStates from ``PartitionedDB``, stacked
    per-tenant schedules).  One dispatch drives all tenants' segments."""
    fn = functools.partial(run_schedule, cfg=cfg, n_batches=n_batches,
                           batch=batch, t0=t0)
    return jax.vmap(fn)(estates, gsts, rngs, scheds)


@functools.lru_cache(maxsize=256)
def jit_run_tenants(cfg: engine.EngineConfig, n_batches: int, batch: int,
                    donate: bool = True):
    fn = functools.partial(run_tenants, cfg=cfg, n_batches=n_batches,
                           batch=batch)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def run_tenants_sharded(estates: engine.EngineState, gsts: GenState,
                        rngs: jax.Array, scheds: PhaseSchedule,
                        cfg: engine.EngineConfig, mesh, *,
                        n_batches: int, batch: int,
                        t0: jax.Array | int = 0):
    """``run_tenants`` over a device mesh: the P-leading inputs are
    sharded on the mesh's partition axis (``cfg.mesh_axis``) and each
    device runs the local vmap over its own P/D tenants under
    ``shard_map`` -- generation + execution of every tenant's whole
    segment is ONE dispatch across N devices.  Tenant segments are
    shared-nothing (tenant i is pinned to partition i), so no collective
    appears in the loop and the result is bit-identical to the vmapped
    ``run_tenants`` on one device -- the mesh parity tests pin it."""
    from jax.sharding import PartitionSpec as P
    axis = cfg.mesh_axis
    fn = functools.partial(run_schedule, cfg=cfg, n_batches=n_batches,
                           batch=batch)

    def local(est, g, r, sch, t0):
        return jax.vmap(functools.partial(fn, t0=t0))(est, g, r, sch)

    spec, rep = P(axis), P()
    sm = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, spec, rep),
                       out_specs=(spec, spec, spec, spec),
                       check_vma=False)
    return sm(estates, gsts, rngs, scheds, jnp.asarray(t0, jnp.int32))


@functools.lru_cache(maxsize=256)
def jit_run_tenants_sharded(cfg: engine.EngineConfig, n_batches: int,
                            batch: int, mesh, donate: bool = True):
    """Jitted ``run_tenants_sharded``; the mesh is part of the cache key
    (``jax.sharding.Mesh`` is hashable), so facades sharing a config AND
    a mesh share compiles."""
    fn = functools.partial(run_tenants_sharded, cfg=cfg, mesh=mesh,
                           n_batches=n_batches, batch=batch)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())
