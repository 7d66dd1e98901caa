"""PrismDB facade: the paper's client interface over the functional core.

Both facades are thin shells over ``repro.core.engine``: a client batch is
ONE jitted ``engine_step`` dispatch that performs the data op and the whole
compaction control plane (rate limit, watermark loop, §5.3 read-triggered
policy) on device -- no host syncs in the hot loop.  ``PartitionedDB`` is
the same core vmapped over P shared-nothing partitions (paper §4.1): each
partition owns a hash slice of the key space with its own tracker, mapper,
buckets and runs; single-partition is just P = 1 of the vmapped path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import engine, policy, tiers
from repro.core.engine import EngineConfig, OpBatch
from repro.core.tiers import TierConfig
from repro.core.utils import pack_buckets, part_of_key
from repro.obs import export as obs_export
from repro.obs.state import ObsConfig

PART_AXIS = "part"          # mesh axis name for the partition dimension


def _span(name: str, seq: int):
    """A host span in a profiler trace, on the same clock as the device
    ops; a native no-op while no profiler runs.  ``seq`` is the batch's
    sequence number (``dispatches``), shared by every span of one batch."""
    return jax.profiler.TraceAnnotation(name, seq=seq)


def _sync_obs(obs: ObsConfig | None, cfg: TierConfig) -> ObsConfig:
    """Keep the obs plane's tier count in lockstep with the tier config
    (it sizes the timeline rows and the per-boundary job counters)."""
    obs = obs if obs is not None else ObsConfig()
    if obs.n_tiers != cfg.n_tiers:
        obs = obs._replace(n_tiers=cfg.n_tiers)
    return obs


class PrismDB:
    """Single-partition store. Batched Put/Get/Delete/Scan + compaction.

    ``dispatches`` counts jitted engine calls issued by this facade: in the
    steady state it is exactly one per client batch (the harness reports
    dispatches per 1k ops from it).

    Each client call is a profiler span (``prism.put`` / ``get`` /
    ``delete`` / ``scan``) holding ``prism.make_op`` (building the
    OpBatch, host-to-device copies included) and ``prism.dispatch`` (the
    jitted step call), all tagged with the batch's ``dispatches`` number.

    A single batch can never exceed ``fast_slots`` live keys: the rate
    limiter frees space *before* the insert, but no amount of compaction
    makes the fast tier bigger than itself -- overflow keys in one
    oversized batch are dropped (same ceiling as the pre-fused host loop).
    """

    def __init__(self, cfg: TierConfig, seed: int = 0,
                 pol_cfg: policy.PolicyConfig | None = None,
                 promote: bool = True, precise: bool = False,
                 selection: str = "msc", pin_mode: str = "object",
                 append_only: bool = False, consolidate_every: int = 0,
                 backend: str = "reference",
                 interpret: bool | None = None,
                 obs: ObsConfig | None = None,
                 compaction_quantum: int = 0):
        """``append_only`` models LSM semantics for the baselines: every
        update appends a new version (memtable/L0), so fast-tier space is
        consumed by total write VOLUME, not unique keys -- compactions must
        run at write rate.  PrismDB's slab layout updates in place
        (append_only=False), which is a core §3 advantage.  Implemented as
        virtual fill accounting; duplicates merge away at compaction.

        ``consolidate_every``: rebuild the sorted indexes from scratch
        every N engine steps (hot paths maintain them incrementally; 0
        disables the fallback, which is exact anyway).

        ``backend``: "reference" (pure jnp, default) or "pallas" (route
        tracker updates + approx-MSC scoring through the kernels);
        ``interpret=None`` auto-picks the Pallas interpreter on CPU only.
        """
        self.cfg = cfg
        self.append_only = append_only
        self.ecfg = EngineConfig(
            tier=cfg, pol=pol_cfg or policy.PolicyConfig(), promote=promote,
            precise=precise, selection=selection, pin_mode=pin_mode,
            append_only=append_only, consolidate_every=consolidate_every,
            backend=backend, interpret=interpret,
            obs=_sync_obs(obs, cfg),
            compaction_quantum=compaction_quantum)
        self.estate = engine.init(self.ecfg, jax.random.PRNGKey(seed))
        self._step = engine.jit_step(self.ecfg)
        self._run = engine.jit_run_ops(self.ecfg)
        self.dispatches = 0

    # -- engine-state views ------------------------------------------------
    # Snapshot copies: engine-state buffers are DONATED to the next
    # dispatch, so a live view handed out here would be invalidated by the
    # next put/get.  Copies keep the old read-anytime contract.
    @property
    def state(self) -> tiers.TierState:
        return engine.dealias(self.estate.tier)

    @property
    def pol(self) -> policy.PolicyState:
        return engine.dealias(self.estate.pol)

    @property
    def promote(self) -> bool:
        return self.ecfg.promote

    @property
    def precise(self) -> bool:
        return self.ecfg.precise

    # -- client ops --------------------------------------------------------
    def _op(self, kind: int, keys, vals=None, valid=None, aux=None):
        with _span("prism.make_op", self.dispatches):
            return engine.make_op(kind, keys, vals, valid, aux,
                                  value_width=self.cfg.value_width)

    def _dispatch(self, op: OpBatch):
        with _span("prism.dispatch", self.dispatches):
            self.estate, res = self._step(self.estate, op)
        self.dispatches += 1
        return res

    def put(self, keys, vals=None, valid=None):
        with _span("prism.put", self.dispatches):
            self._dispatch(self._op(engine.PUT, keys, vals, valid))

    def get(self, keys, valid=None):
        with _span("prism.get", self.dispatches):
            res = self._dispatch(self._op(engine.GET, keys, valid=valid))
        return res.vals, res.found, res.src

    def delete(self, keys, valid=None):
        with _span("prism.delete", self.dispatches):
            self._dispatch(self._op(engine.DELETE, keys, valid=valid))

    def scan(self, lo: int, n: int):
        return tiers.scan(self.estate.tier, jnp.int32(lo), n)

    def scan_ops(self, starts, lens, valid=None):
        """Batched bounded range scans through the fused engine step
        (YCSB-E path).  Returns per-lane live-key counts."""
        with _span("prism.scan", self.dispatches):
            res = self._dispatch(self._op(engine.SCAN, starts, valid=valid,
                                          aux=lens))
        return res.src

    def run_ops(self, ops: OpBatch):
        """Drive a stacked op stream (leading axis = batches) in ONE
        dispatch via ``lax.scan``; returns stacked OpResults."""
        self.estate, res = self._run(self.estate, ops)
        self.dispatches += 1
        return res

    # -- device-resident workloads ----------------------------------------
    def reset_workload(self, seed: int = 0) -> None:
        """(Re)start the workload stream: generator state + its rng."""
        from repro import workloads
        self._gen = workloads.init_gen(self.cfg.key_space)
        self._wrng = jax.random.PRNGKey(seed)
        self._wt = 0

    def run_workload(self, work, n_batches: int, batch: int):
        """Run ``n_batches`` steps of a WorkloadSpec / PhaseSchedule with
        generation fused into the engine scan: ONE dispatch for the whole
        segment.  Successive calls continue the same stream/timeline
        (``reset_workload`` restarts it); returns stacked StepStats."""
        from repro import workloads
        if getattr(self, "_gen", None) is None:
            self.reset_workload()
        sched = workloads.as_schedule(work, n_batches)
        fn = workloads.jit_run_schedule(self.ecfg, n_batches, batch)
        self.estate, self._gen, self._wrng, stats = fn(
            self.estate, self._gen, self._wrng, sched, t0=self._wt)
        self._wt += n_batches
        self.dispatches += 1
        return stats

    # -- introspection -------------------------------------------------------
    @property
    def counters(self) -> dict:
        """Object-unit counters + derived byte counters (python ints, no
        overflow), and with the obs plane on its compaction-burst
        counters: ``jobs_by_trigger`` (jobs per ``TRIGGER_NAMES`` kind)
        and ``steps_by_compactions`` (engine steps per ``step_comp_hist``
        bucket).  This is a host readback -- introspection only, never
        on the hot path."""
        c = tiers.counters_dict(self.estate.tier.ctr)
        vb = self.cfg.value_bytes
        c["fast_bytes_read"] = c["fast_reads"] * vb
        c["fast_bytes_written"] = c["fast_writes"] * vb
        c["slow_bytes_read"] = c["slow_reads"] * vb
        c["slow_bytes_written"] = c["slow_writes"] * vb
        if self.ecfg.obs.enabled:
            jobs, steps = jax.device_get((self.estate.obs.ev_jobs_t,
                                          self.estate.obs.step_comp_hist))
            c["jobs_by_trigger"] = [int(x) for x in jobs]
            c["steps_by_compactions"] = [int(x) for x in steps]
        return c

    def occupancy(self) -> float:
        return float(tiers.fast_occupancy(self.estate.tier))

    def obs_snapshot(self) -> dict:
        """Host snapshot of the device-resident observability plane
        (latency histograms, counter timeline, compaction events); one
        readback, introspection only."""
        return obs_export.snapshot(self.estate.obs)


def route_batch(keys: jax.Array, p: int, per_part: int
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter a batch into [P, per_part] padded per-partition batches.

    Returns (routed, valid, dropped): keys beyond ``per_part`` in one
    partition do not fit the pad and are counted in the PER-PARTITION
    ``dropped`` i32[P] vector, never silently lost -- a skewed tenant
    whose keys pile onto one partition is visible as that partition's
    drop count, not a global blur.  The partition hash is
    ``utils.part_of_key`` (splitmix-style mix, then modulo): the mix
    step avalanches every input bit, so structured key patterns
    (sequential ranges, strided tenants) can't alias onto one partition
    the way a plain ``key % p`` would.  The mesh-sharded exchange
    (``distributed.collectives.exchange_keys``) uses the SAME hash, so
    both routing paths agree on key placement bit-for-bit."""
    part = part_of_key(keys, p)
    return pack_buckets(keys, part, p, per_part)


def _vmapped_op(estate, routed, valid, kind, cfg: EngineConfig):
    """vmap ``engine_step`` over the leading partition axis of
    ``estate`` / ``routed`` / ``valid``; shared by both routing paths."""
    vals = jnp.broadcast_to(
        routed[..., None].astype(jnp.float32),
        (*routed.shape, cfg.tier.value_width))
    op = OpBatch(kind=jnp.int32(kind), keys=routed, vals=vals, valid=valid,
                 aux=jnp.zeros_like(routed))
    step = functools.partial(engine.engine_step, cfg=cfg)
    return jax.vmap(step, in_axes=(0, OpBatch(None, 0, 0, 0, 0)))(
        estate, op)


def _partitioned_step(estate, keys, kind: int, cfg: EngineConfig, p: int,
                      per_part: int):
    """Route + vmapped engine_step: one dispatch for the whole batch."""
    routed, valid, dropped = route_batch(keys, p, per_part)
    estate, res = _vmapped_op(estate, routed, valid, kind, cfg)
    return estate, res, dropped


def _mesh_step(estate, keys, valid, kind, cfg: EngineConfig, p: int,
               lp: int, cap: int):
    """One routed client batch INSIDE shard_map: the device-side ragged
    exchange sends every key to its owning partition, then the local
    partitions (``lp`` per device) run the same vmapped ``engine_step``
    as the fallback path.  One dispatch, N devices, no host scatter."""
    from repro.distributed import collectives
    routed, rvalid, dropped = collectives.exchange_keys(
        keys, n_parts=p, cap=cap, axis_name=cfg.mesh_axis,
        local_parts=lp, valid=valid)
    estate, res = _vmapped_op(estate, routed, rvalid, kind, cfg)
    return estate, res, dropped


def resolve_mesh(mesh, n_partitions: int):
    """Resolve the ``mesh`` constructor arg of ``PartitionedDB``.

    ``None`` -> single-device vmap fallback.  ``"auto"`` -> a 1-D
    ``Mesh`` over the largest device count that divides ``n_partitions``
    (1 device -> ``None``: the vmap path IS the P=1/no-mesh fallback).
    A ``jax.sharding.Mesh`` is validated (must carry a ``part`` axis
    whose size divides ``n_partitions``) and used as given."""
    if mesh is None:
        return None
    if isinstance(mesh, str):
        if mesh != "auto":
            raise ValueError(f"mesh={mesh!r}: expected None, 'auto' or a "
                             "jax.sharding.Mesh")
        devs = jax.devices()
        d = max(k for k in range(1, min(n_partitions, len(devs)) + 1)
                if n_partitions % k == 0)
        if d == 1:
            return None
        return jax.sharding.Mesh(np.asarray(devs[:d]), (PART_AXIS,))
    if PART_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh must have a '{PART_AXIS}' axis, got "
                         f"{mesh.axis_names}")
    d = mesh.shape[PART_AXIS]
    if n_partitions % d != 0:
        raise ValueError(f"{d} mesh devices must divide "
                         f"{n_partitions} partitions")
    return mesh


def _init_on_mesh(init, rngs, mesh, lp: int):
    """Build each device's ``lp`` partitions ON that device, then assemble
    the ``part``-sharded global state: no device ever holds more than
    its own partitions' pools (a deployment-sized partition fills a good
    share of one chip's memory)."""
    from repro.distributed import sharding as shd
    devs = list(mesh.devices.reshape(-1))
    local = []
    for i, dev in enumerate(devs):
        with jax.default_device(dev):
            local.append(init(jax.device_put(rngs[i * lp:(i + 1) * lp],
                                             dev)))
    shardings = shd.leading_axis_sharding(jax.eval_shape(init, rngs), mesh)
    return jax.tree.map(
        lambda sh, *xs: jax.make_array_from_single_device_arrays(
            (xs[0].shape[0] * len(devs),) + xs[0].shape[1:], sh, list(xs)),
        shardings, *local)


class PartitionedDB:
    """Shared-nothing partitions (paper §4.1, Fig. 11d): vmap on one
    device, ``shard_map`` over a device mesh when one is available.

    Keys are routed by hash; every partition executes the same jitted
    ``engine_step`` on its own slice (masked for load imbalance within the
    batch).  ``dropped`` counts keys that exceeded a partition's pad --
    surfaced per partition, not silently lost.

    ``mesh``: ``None`` = the single-device vmap path (the P=1/no-mesh
    fallback, bit-exact against the sharded path); ``"auto"`` (default) =
    shard over the largest available device count dividing
    ``n_partitions`` (falls back to vmap on one device, so the default
    changes nothing in single-device environments); or an explicit
    ``jax.sharding.Mesh`` with a ``part`` axis.  On a mesh, each device
    owns ``n_partitions / D`` partitions' full engine state (sharded via
    the size-aware ``part`` logical-axis rule), a client batch is split
    across devices, and the ragged all_to_all exchange in
    ``distributed.collectives`` hash-routes every key to its owning
    partition entirely device-side: one dispatch per batch across N
    devices, no host-side scatter/gather."""

    def __init__(self, cfg: TierConfig, n_partitions: int, seed: int = 0,
                 promote: bool = True,
                 pol_cfg: policy.PolicyConfig | None = None,
                 backend: str = "reference",
                 interpret: bool | None = None,
                 obs: ObsConfig | None = None,
                 compaction_quantum: int = 0,
                 mesh="auto"):
        self.cfg = cfg
        self.p = n_partitions
        self.mesh = resolve_mesh(mesh, n_partitions)
        self.lp = (n_partitions // self.mesh.shape[PART_AXIS]
                   if self.mesh is not None else n_partitions)
        self.ecfg = EngineConfig(
            tier=cfg, pol=pol_cfg or policy.PolicyConfig(), promote=promote,
            backend=backend, interpret=interpret,
            obs=_sync_obs(obs, cfg),
            compaction_quantum=compaction_quantum,
            mesh_axis=PART_AXIS if self.mesh is not None else None)
        rngs = jax.random.split(jax.random.PRNGKey(seed), n_partitions)
        # one program per device: no pool is built twice (engine.init)
        init = jax.jit(jax.vmap(functools.partial(engine.init, self.ecfg)))
        self._dropped = jnp.zeros((n_partitions,), jnp.int32)
        if self.mesh is not None:
            self.estate = _init_on_mesh(init, rngs, self.mesh, self.lp)
            self._mesh_steps = {}
        else:
            self.estate = init(rngs)
            self._step = jax.jit(
                functools.partial(_partitioned_step, cfg=self.ecfg,
                                  p=n_partitions),
                static_argnames=("kind", "per_part"))
        self.dispatches = 0

    @property
    def state(self) -> tiers.TierState:
        # snapshot copy: see PrismDB.state (donation invalidates live views)
        return engine.dealias(self.estate.tier)

    @property
    def dropped(self) -> int:
        """Total keys that exceeded a partition pad (routing overflow)."""
        return int(jnp.sum(self._dropped))

    @property
    def dropped_per_partition(self) -> list:
        """Routing-overflow drops per partition: a skewed tenant whose
        keys alias onto one partition shows up HERE (the global total
        hides exactly that failure mode)."""
        return [int(x) for x in np.asarray(self._dropped)]

    def _mesh_dispatch(self, keys, kind: int):
        """Routed client batch over the mesh: pad the batch to the
        device count, shard it, exchange device-side, step.  The
        (padded-width, capacity) pair keys a small jit cache -- client
        batch sizes are few and static in practice."""
        from jax.sharding import PartitionSpec as P
        d = self.mesh.shape[PART_AXIS]
        b = keys.shape[0]
        bpad = -(-b // d) * d
        # same capacity policy as the vmap path's route_batch pad (at
        # D=1 the layouts are bit-identical: the parity tests pin it)
        cap = max(2 * (bpad // d) // self.p, 8)
        fn = self._mesh_steps.get((bpad, cap))
        if fn is None:
            local = functools.partial(_mesh_step, cfg=self.ecfg, p=self.p,
                                      lp=self.lp, cap=cap)
            sm = jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(PART_AXIS), P(PART_AXIS), P(PART_AXIS), P()),
                out_specs=(P(PART_AXIS), P(PART_AXIS), P()),
                check_vma=False)
            fn = jax.jit(sm, donate_argnums=(0,))
            self._mesh_steps[(bpad, cap)] = fn
        kpad = jnp.zeros((bpad,), jnp.int32).at[:b].set(keys)
        vpad = jnp.zeros((bpad,), bool).at[:b].set(True)
        self.estate, res, dropped = fn(self.estate, kpad, vpad,
                                       jnp.int32(kind))
        return res, dropped

    def _dispatch(self, keys, kind: int):
        keys = jnp.asarray(keys, jnp.int32)
        if self.mesh is not None:
            res, dropped = self._mesh_dispatch(keys, kind)
        else:
            per = max(2 * keys.shape[0] // self.p, 8)
            self.estate, res, dropped = self._step(
                self.estate, keys, kind=kind, per_part=per)
        self._dropped = self._dropped + dropped
        self.dispatches += 1
        return res

    def put(self, keys):
        self._dispatch(keys, engine.PUT)

    def get(self, keys):
        res = self._dispatch(keys, engine.GET)
        return res.vals, res.found, res.src

    # -- device-resident multi-tenant workloads ---------------------------
    def reset_workload(self, seed: int = 0) -> None:
        from repro import workloads
        self._gen = jax.vmap(lambda _: workloads.init_gen(
            self.cfg.key_space))(jnp.arange(self.p))
        self._wrng = jax.random.split(jax.random.PRNGKey(seed), self.p)
        if self.mesh is not None:
            # commit generator/rng state to the mesh UP FRONT: the first
            # dispatch's outputs come back part-sharded, and a jit cache
            # keys on input shardings -- uncommitted inputs here would buy
            # a full recompile on the SECOND run_workload call
            from repro.distributed import sharding as shd
            self._gen = jax.device_put(
                self._gen, shd.leading_axis_sharding(self._gen, self.mesh))
            self._wrng = jax.device_put(
                self._wrng,
                shd.leading_axis_sharding(self._wrng, self.mesh))
        self._wt = 0

    def run_workload(self, works, n_batches: int, batch: int):
        """Multi-tenant mixes: tenant i (= partition i) runs its own
        WorkloadSpec / PhaseSchedule over its own key slice, all tenants
        vmapped under ONE dispatch.  ``works`` is one workload shared by
        every tenant or a length-P sequence (phase counts must match, the
        vmap axis is stacked).  Returns StepStats with a leading tenant
        axis."""
        from repro import workloads
        if getattr(self, "_gen", None) is None:
            self.reset_workload()
        if isinstance(works, (workloads.WorkloadSpec,
                              workloads.PhaseSchedule)):
            works = [works] * self.p        # specs are NamedTuples: test
        works = list(works)                 # identity before sequence-ness
        assert len(works) == self.p, (len(works), self.p)
        scheds = [workloads.as_schedule(w, n_batches) for w in works]
        counts = [workloads.n_phases(s) for s in scheds]
        assert len(set(counts)) == 1, \
            f"tenant schedules must have equal phase counts, got {counts}"
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *scheds)
        if self.mesh is not None:
            # tenant i IS partition i: schedules pin to their partition's
            # device, the whole multi-tenant segment is one shard_map
            # dispatch across the mesh, no cross-partition traffic
            fn = workloads.jit_run_tenants_sharded(
                self.ecfg, n_batches, batch, self.mesh)
        else:
            fn = workloads.jit_run_tenants(self.ecfg, n_batches, batch)
        self.estate, self._gen, self._wrng, stats = fn(
            self.estate, self._gen, self._wrng, stacked, t0=self._wt)
        self._wt += n_batches
        self.dispatches += 1
        return stats

    @property
    def counters(self) -> dict:
        return tiers.counters_dict(self.estate.tier.ctr,
                                   partitioned=True)

    def obs_snapshot(self) -> dict:
        """Merged cross-partition snapshot: the per-partition histograms
        sum (the reason the obs plane uses histograms, not reservoirs);
        timelines/event rings stay per partition.  Mesh-sharded states
        merge the same way -- the single ``device_get`` gathers the
        ``part``-sharded leaves across the mesh, so the vmapped and
        shard_map paths produce identical snapshots."""
        return obs_export.snapshot(self.estate.obs)
