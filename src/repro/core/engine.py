"""Device-resident engine step: the fused put/get/compact control plane.

Before this module, every facade (``PrismDB``, ``PartitionedDB``, the
serving engine, the embedding store) drove its own compaction loop from
Python, blocking on device syncs (``int(free_slots)``, ``bool(needs)``)
between every batch.  The paper's throughput claim rests on keeping the
compaction control loop OFF the client's critical path (§4.2, §5.3); the
JAX analogue is to run the whole control plane inside one jit so a client
batch -- data op, rate limiting, watermark compactions, the §5.3
read-triggered policy, and payload mirroring -- is a single dispatch.

Building blocks (all jit-/vmap-/scan-safe, static shapes):

  ``EngineState``   unified pytree: TierState + PolicyState + rng +
                    append-only virtual fill + an arbitrary ``payload``
                    pytree mirrored through compactions (KV pages,
                    embedding rows; ``()`` when the store is metadata-only)
  ``engine_step``   one client batch: every point op kind flows
                    through one masked structure-of-arrays pass
                    (``tiers.apply_point_ops``), the scan lane is a
                    kind-gated ``lax.cond`` that returns counts only, and
                    the maintenance plane is gated ``lax.while_loop``s.
                    No ``lax.switch``/``lax.cond`` ever carries pool-sized
                    state: on XLA CPU each such branch materializes an
                    O(pool) pass-through copy per step, which made client
                    batches scale with ``slow_slots`` instead of batch
                    size (tests/test_hlo_budget.py pins this down)
  ``run_ops``       ``lax.scan`` over a stacked op stream: a whole
                    workload segment under one dispatch
  ``maintenance``   the WHOLE maintenance plane -- §4.2 rate limit,
                    watermark hysteresis, §5.3 policy budget -- as one
                    bounded, kind-gated ``lax.while_loop``; reused by
                    the serving engine and the embedding store around
                    their own data ops (``maintain`` / ``read_policy``
                    are single-concern wrappers)

``mirror(payload, movement) -> payload`` replays each compaction's
``Movement`` on the payload pools inside the same jitted step -- the
tier_compact kernel's role on TPU.

``EngineConfig.backend`` statically routes the three kernelized hot-path
primitives -- tracker updates (clock_update), approx-MSC scoring
(msc_score), and the mirrors' Movement replay (tier_compact) -- through
``repro.kernels``; ``"reference"`` (default) traces the exact pre-
dispatch jnp path, bit-identical HLO included.  The dispatch is resolved
at trace time from the config (which keys every jit cache here), never
from traced values.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import backend as backend_mod
from repro.core import compaction, policy, tiers
from repro.core.tiers import TierConfig, TierState
from repro.obs import state as obs_plane
from repro.obs.state import ObsConfig

PUT, GET, DELETE, SCAN = 0, 1, 2, 3

MirrorFn = Callable[[Any, compaction.Movement], Any]


class EngineConfig(NamedTuple):
    """Static engine parameters (closure constants under jit)."""
    tier: TierConfig
    pol: policy.PolicyConfig = policy.PolicyConfig()
    promote: bool = True
    precise: bool = False
    selection: str = "msc"
    pin_mode: str = "object"
    append_only: bool = False
    scan_chunk: int = 32        # index-window entries per tier per scan lane
    max_rounds: int = 256       # compaction-round bound per engine step
                                # (matches the old host rate-limit loop; the
                                # while_loop body is traced once regardless)
    consolidate_every: int = 0  # full index rebuild every N engine steps
                                # (0 = never: incremental maintenance is
                                # exact; the fallback is hygiene for pad
                                # entries, counted in ctr.consolidations)
    backend: str = "reference"  # hot-path primitive dispatch: "reference"
                                # (pure jnp) or "pallas" (clock_update /
                                # msc_score / tier_compact kernels).
                                # STATIC: resolved at trace time and keyed
                                # by the config hash -- never a lax.cond
                                # over pool state (PR 4 invariant)
    interpret: bool | None = None  # Pallas interpret knob; None = auto
                                # (interpreter on CPU, compiled on GPU/TPU
                                # -- see core/backend.py)
    obs: ObsConfig = ObsConfig()  # device-resident observability plane;
                                # static (hashable) so enabled/sizes key
                                # the jit caches.  The ObsState rides in
                                # EngineState: zero extra dispatches
    mesh_axis: str | None = None  # shard_map mesh axis this engine runs
                                # under (None = single device / vmap).
                                # The engine step itself is shared-nothing
                                # -- no collective ever appears in the
                                # hot loop; the axis name is what the
                                # FACADE's routing collectives
                                # (distributed.collectives.exchange_keys:
                                # the ragged all_to_all + the per-
                                # partition drop psum) key on, and being
                                # part of the config it keys every jit
                                # cache so sharded and unsharded tracings
                                # of the same tier config never alias
    compaction_quantum: int = 0  # >0: preemptible micro-step compaction.
                                # A triggered job still COMMITS its
                                # logical transition at the trigger (so
                                # pools/indexes/counters/final state are
                                # bit-identical for any quantum), but its
                                # physical migration + modeled-I/O
                                # attribution ride the in-flight carry
                                # (EngineState.comp) and drain at most
                                # this many merged rows per engine step.
                                # 0 = run-to-completion (today's exact
                                # code path: the carry machinery is not
                                # even traced)


class EngineState(NamedTuple):
    """Everything the control plane owns, as one donatable pytree."""
    tier: TierState
    pol: policy.PolicyState
    rng: jax.Array
    virtual_extra: jax.Array    # i32: append-only phantom fast-tier fill
    steps: jax.Array            # i32: engine steps (consolidation clock)
    payload: Any = ()           # pytree mirrored through compactions
    obs: Any = ()               # ObsState when cfg.obs.enabled, else ()
    comp: Any = ()              # compaction.InFlight when
                                # cfg.compaction_quantum > 0, else ()


class OpBatch(NamedTuple):
    """One client batch.  ``kind`` is a traced scalar so an op stream can be
    stacked and scanned; ``vals`` is ignored by get/delete/scan; ``aux`` is
    the per-lane range length for scan, ignored otherwise."""
    kind: jax.Array             # i32 scalar: PUT / GET / DELETE / SCAN
    keys: jax.Array             # i32[B] (scan: range start keys)
    vals: jax.Array             # f32[B, V]
    valid: jax.Array            # bool[B]
    aux: jax.Array              # i32[B] (scan: requested range length)


class OpResult(NamedTuple):
    vals: jax.Array             # f32[B, V] (zeros unless get)
    found: jax.Array            # bool[B]
    src: jax.Array              # i32[B]: get 0=fast 1=slow -1=miss;
                                #         scan: live keys returned


def dealias(tree):
    """Copy every leaf into its own buffer.  Freshly-built states reuse one
    zero buffer across fields (``Counters.zeros``); donation rejects a
    buffer donated twice, so donatable states must hold unique buffers."""
    return jax.tree.map(
        lambda x: jnp.array(x) if isinstance(x, jax.Array) else x, tree)


# One program builds a fresh tier state, so each leaf lands in a buffer
# of its own on the device.  Built op by op and then copied (``dealias``),
# every pool would be held twice at once.
_init_tier = jax.jit(tiers.init, static_argnums=0)


def init(cfg: EngineConfig, rng: jax.Array, payload: Any = (),
         tier: TierState | None = None) -> EngineState:
    backend_mod.check(cfg.backend)
    rest = dealias(EngineState(
        tier=tier, pol=policy.init(), rng=rng,
        virtual_extra=jnp.zeros((), jnp.int32),
        steps=jnp.zeros((), jnp.int32), payload=payload,
        obs=obs_plane.init(cfg.obs) if cfg.obs.enabled else (),
        comp=(compaction.init_inflight(cfg.tier)
              if cfg.compaction_quantum > 0 else ())))
    return rest if tier is not None else rest._replace(
        tier=_init_tier(cfg.tier))


def make_op(kind: int, keys: jax.Array, vals: jax.Array | None = None,
            valid: jax.Array | None = None, aux: jax.Array | None = None, *,
            value_width: int) -> OpBatch:
    """Build an OpBatch with the facade defaults (value = broadcast key)."""
    keys = jnp.asarray(keys, jnp.int32)
    if vals is None:
        vals = jnp.broadcast_to(keys[:, None].astype(jnp.float32),
                                (keys.shape[0], value_width))
    if valid is None:
        valid = jnp.ones(keys.shape, bool)
    if aux is None:
        aux = jnp.zeros(keys.shape, jnp.int32)
    return OpBatch(kind=jnp.int32(kind), keys=keys,
                   vals=jnp.asarray(vals, jnp.float32), valid=valid,
                   aux=jnp.asarray(aux, jnp.int32))


# ------------------------------------------------------------ compaction
# Device scopes (``jax.named_scope``) name each layer of the step in the
# compiled program's op metadata, so a profiler trace assigns device time
# to ``maintenance``, ``compact`` (and its phases), ``drain``,
# ``point_ops``, ``scan_lane``, ``consolidate`` and ``obs_record``.  They
# are metadata only: the computation is the same with or without them.

@jax.named_scope("compact")
def _compact1(state: EngineState, cfg: EngineConfig,
              mirror: MirrorFn | None,
              force_pin_keys: jax.Array | None,
              trigger: jax.Array | None = None) -> EngineState:
    """One compaction + payload mirroring + append-only fill accounting
    (+ one observability event when the obs plane is enabled).

    With ``cfg.compaction_quantum > 0`` the logical transition still
    commits HERE (bit-identical state for any quantum), but the job's
    Movement rows and I/O categories are staged into the in-flight carry
    for ``engine_step`` to drain, and the event logged is an EV_START
    with zero ``io_us`` -- the cost lands on the draining steps."""
    quantized = cfg.compaction_quantum > 0
    want_mv = quantized or mirror is not None
    rng, sub = jax.random.split(state.rng)
    out = compaction.compact_once(
        state.tier, cfg.tier, rng=sub, promote=cfg.promote,
        precise=cfg.precise, selection=cfg.selection, pin_mode=cfg.pin_mode,
        with_movement=want_mv, force_pin_keys=force_pin_keys,
        backend=cfg.backend, interpret=cfg.interpret)
    if not want_mv:
        tier, stats = out
        payload = state.payload
    else:
        tier, stats, mv = out
        # payload mirrors replay at commit, NOT per quantum: deferring
        # them is unsound (a later step may clobber the source pages) --
        # the paper's §6 partition lock covers exactly this window
        payload = (state.payload if mirror is None
                   else mirror(state.payload, mv))
    ve = state.virtual_extra
    if cfg.append_only:
        # phantom versions merge away only when the compaction actually
        # merged duplicates: decay by the measured superseded-copy count,
        # not by key-range coverage (which decayed even on no-op merges).
        ve = jnp.maximum(ve - stats.n_superseded, 0)
    trig = (jnp.int32(obs_plane.TRIG_POLICY) if trigger is None
            else trigger)
    comp = state.comp
    if quantized:
        comp = compaction.stage_inflight(comp, stats, mv, trig)
    obs = state.obs
    if cfg.obs.enabled:
        if quantized:
            obs = obs_plane.record_compaction(
                obs, cfg.obs, step=state.steps, trigger=trig, stats=stats,
                kind=obs_plane.EV_START, io_us=jnp.float32(0.0))
        else:
            obs = obs_plane.record_compaction(
                obs, cfg.obs, step=state.steps, trigger=trig, stats=stats)
    return state._replace(tier=tier, rng=rng, virtual_extra=ve,
                          payload=payload, obs=obs, comp=comp)


@jax.named_scope("compact")
def _deep_tick(state: EngineState, cfg: EngineConfig, boundary: int,
               wm_gate, need: int = 0) -> EngineState:
    """Watermark hysteresis at one DEEP (run-to-run) boundary >= 1:
    while tier ``boundary`` sits above the high watermark, migrate its
    best-scoring run down into tier ``boundary + 1`` until occupancy
    drops below the low watermark (same §4.2 hysteresis as the slab
    boundary, bounded by ``max_rounds``).  Only traced when
    ``cfg.tier.n_tiers > 2`` -- the two-tier graph is untouched.  Deep
    merges move run rows wholesale, so there is no payload mirror and no
    §5.3 policy at these boundaries (promotion targets tier i-1 only at
    the slab boundary).

    ``need`` (static) additionally drains until the tier has that many
    FREE slots (or is empty): free slots are hard capacity -- a merge
    landing in a full middle tier drops rows -- so the maintenance loop
    pre-drains each tier's worst-case single-merge inflow before
    compacting the boundary above it."""
    wm0 = wm_gate & compaction.tier_over_watermark(state.tier, cfg.tier,
                                                   boundary)

    def pressure(s):
        keys = s.tier.keys[boundary]
        free = jnp.sum((keys < 0).astype(jnp.int32))
        return free < need

    def cond(carry):
        s, rounds = carry
        # a migratable run must exist: without one the merge is a no-op
        # and the loop would burn max_rounds doing (counted) nothing
        can = jnp.any(s.tier.dir_active[boundary - 1])
        return (rounds < cfg.max_rounds) & can & (
            (wm0 & ~compaction.tier_below_low(s.tier, cfg.tier, boundary))
            | pressure(s))

    def body(carry):
        s, rounds = carry
        if boundary + 1 < cfg.tier.n_tiers - 1:
            # the receiving tier is itself a middle tier: give it the
            # same worst-case headroom first (recursion ends at the
            # last boundary, whose receiver is the capacity tier)
            s = _deep_tick(s, cfg, boundary + 1, True,
                           need=2 * cfg.tier.run_size)
        tier, stats = compaction.compact_boundary(
            s.tier, cfg.tier, boundary, cost=cfg.obs.cost)
        s = s._replace(tier=tier)
        if cfg.obs.enabled:
            s = s._replace(obs=obs_plane.record_compaction(
                s.obs, cfg.obs, step=s.steps,
                trigger=jnp.int32(obs_plane.TRIG_WATERMARK),
                stats=stats, boundary=boundary))
        return s, rounds + 1

    state, _ = lax.while_loop(cond, body,
                              (state, jnp.zeros((), jnp.int32)))
    return state


@jax.named_scope("maintenance")
def maintenance(state: EngineState, cfg: EngineConfig, *,
                need: jax.Array | int = 0,
                wm_gate: jax.Array | bool = True,
                policy_enable: jax.Array | bool = True,
                mirror: MirrorFn | None = None,
                force_pin_keys: jax.Array | None = None) -> EngineState:
    """The WHOLE maintenance plane as ONE bounded while_loop.

    Fuses the §4.2 rate limit (compact while usable fast slots -- free
    minus append-only virtual fill -- are below ``need``: writes stall
    until the compaction job frees space), the watermark hysteresis loop
    (on crossing the high watermark, continue until below the low one),
    and the §5.3 policy budget into a single ``_compact1`` loop bounded
    by ``cfg.max_rounds``.

    One loop instead of three matters twice inside the workload scan:
    the compaction body is traced/compiled once per step instead of
    three times, and XLA CPU pays the pool-sized carry-tuple copies for
    one nested while instead of three (charged even at zero iterations).
    Every gate may be a traced boolean, so the branchless engine step
    masks by op kind with no ``lax.cond`` -- whose taken-branch would
    materialize an O(pool) copy of the engine state every step.

    The policy machine only advances when ``policy_enable`` (the engine
    step passes reads); the watermark trigger only arms when ``wm_gate``.
    """
    need = jnp.asarray(need, jnp.int32)
    total = (state.tier.ctr.gets + state.tier.ctr.puts
             + state.tier.ctr.scans)
    pol_next, go = policy.step(state.pol, state.tier, cfg.pol,
                               total_ops=total)
    pol = jax.tree.map(lambda a, b: jnp.where(policy_enable, a, b),
                       pol_next, state.pol)
    state = state._replace(pol=pol)
    n_pol = jnp.where(policy_enable & go & (pol_next.phase == policy.ACTIVE),
                      cfg.pol.compactions_per_epoch_step, 0)
    wm0 = wm_gate & (tiers.fast_occupancy(state.tier)
                     >= cfg.tier.high_watermark)

    def usable(s: EngineState) -> jax.Array:
        return tiers.free_fast_slots(s.tier) - s.virtual_extra

    def cond(carry):
        s, rounds = carry
        occ = tiers.fast_occupancy(s.tier)
        return (rounds < cfg.max_rounds) & (
            (usable(s) < need)
            | (wm0 & (occ >= cfg.tier.low_watermark))
            | (rounds < n_pol))

    def body(carry):
        s, rounds = carry
        # priority-encoded trigger kind for the obs event ring, mirroring
        # the cond's disjunct order: a compaction freeing write headroom
        # is a rate-limit stall even if the watermark is also armed
        occ = tiers.fast_occupancy(s.tier)
        trig = jnp.where(
            usable(s) < need, jnp.int32(obs_plane.TRIG_RATE_LIMIT),
            jnp.where(wm0 & (occ >= cfg.tier.low_watermark),
                      jnp.int32(obs_plane.TRIG_WATERMARK),
                      jnp.int32(obs_plane.TRIG_POLICY)))
        if cfg.tier.n_tiers > 2:
            # pre-drain BEFORE the slab merge, deepest boundary first:
            # free slots (not watermarks) are the hard capacity of a
            # small middle tier, so each tier is drained to worst-case
            # single-merge headroom (net inflow <= the upstream window
            # cap, 2*run_size) before rows can land on it
            for b in range(cfg.tier.n_tiers - 2, 0, -1):
                s = _deep_tick(s, cfg, b, True, need=2 * cfg.tier.run_size)
        s = _compact1(s, cfg, mirror, force_pin_keys, trigger=trig)
        return (s, rounds + 1)

    state, _ = lax.while_loop(cond, body,
                              (state, jnp.zeros((), jnp.int32)))
    if cfg.tier.n_tiers > 2:
        # deep boundaries cascade top-down so a slab merge that tips
        # tier 1 over its watermark drains within the same step
        for b in range(1, cfg.tier.n_tiers - 1):
            state = _deep_tick(state, cfg, b, wm_gate)
    return state


def maintain(state: EngineState, cfg: EngineConfig,
             need: jax.Array | int = 0, *, mirror: MirrorFn | None = None,
             force_pin_keys: jax.Array | None = None,
             wm_gate: jax.Array | bool = True) -> EngineState:
    """Rate-limit + watermark compactions only (no policy step)."""
    return maintenance(state, cfg, need=need, wm_gate=wm_gate,
                       policy_enable=False, mirror=mirror,
                       force_pin_keys=force_pin_keys)


def read_policy(state: EngineState, cfg: EngineConfig, *,
                mirror: MirrorFn | None = None,
                force_pin_keys: jax.Array | None = None,
                enable: jax.Array | bool = True) -> EngineState:
    """§5.3 read-triggered policy step + its compaction budget only."""
    return maintenance(state, cfg, need=0, wm_gate=False,
                       policy_enable=enable, mirror=mirror,
                       force_pin_keys=force_pin_keys)


# ------------------------------------------------------------ engine step

@jax.named_scope("drain")
def drain_tick(state: EngineState, cfg: EngineConfig) -> EngineState:
    """Drain one compaction quantum from the in-flight carry and log the
    resume/commit event.  No-op (not even traced) when the quantum knob
    is off; called once per engine step (right behind the maintenance
    loop) / serve tick, so the client batch that trips a watermark pays
    one quantum -- not the whole migration."""
    if cfg.compaction_quantum <= 0:
        return state
    fl0 = state.comp

    # count-gated while_loop (at most one iteration), like the watermark
    # compaction loop and _consolidation_tick: on a step with no backlog
    # the body never runs, and scoping the staged-row scatter inside a
    # data-dependent while keeps the hot loop free of pool-shaped copies
    # (a straight-line scatter here costs XLA two slow-pool copies/step).
    zero = jnp.zeros((), jnp.int32)
    def _cond(c):
        ran, _, fl, _, _ = c
        return ~ran & (fl.rem_rows > 0)

    def _body(c):
        _, tier, fl, _, _ = c
        tier, fl, drained, k = compaction.drain_quantum(
            tier, fl, cfg.compaction_quantum,
            backend=cfg.backend, interpret=cfg.interpret)
        return jnp.ones((), bool), tier, fl, drained, k

    _, tier, fl, drained, k = lax.while_loop(
        _cond, _body, (jnp.zeros((), bool), state.tier, fl0,
                       (zero, zero, zero, zero), zero))
    state = state._replace(tier=tier, comp=fl)
    if cfg.obs.enabled:
        from repro.obs.cost import drain_io_us
        state = state._replace(obs=obs_plane.record_drain(
            state.obs, cfg.obs, step=state.steps, trigger=fl0.trigger,
            score=fl0.score, moved=k,
            io_us=drain_io_us(*drained, cfg.obs.cost,
                              cfg.obs.fast_write_amp),
            done=(fl0.rem_rows > 0) & (fl.rem_rows == 0)))
    return state


@jax.named_scope("consolidate")
def _consolidation_tick(state: EngineState, cfg: EngineConfig
                        ) -> EngineState:
    """Periodic full index rebuild, as a count-gated while_loop (runs the
    body at most once; never a cond, which would copy pool state)."""
    due = (state.steps % cfg.consolidate_every) == cfg.consolidate_every - 1

    def cond(carry):
        return (carry[1] == 0) & due

    def body(carry):
        t, _ = carry
        return tiers.consolidate_indexes(t), jnp.int32(1)

    tier, _ = lax.while_loop(cond, body,
                             (state.tier, jnp.zeros((), jnp.int32)))
    return state._replace(tier=tier)


def engine_step(state: EngineState, op: OpBatch, cfg: EngineConfig, *,
                mirror: MirrorFn | None = None,
                force_pin_keys: jax.Array | None = None
                ) -> tuple[EngineState, OpResult]:
    """One client batch, control plane included: a single dispatch.

    ``op.kind`` is a traced scalar, so one compiled body serves
    put/get/delete/scan and a single compilation covers every op stream.
    The point ops are branchless: the kind becomes lane masks of one
    pass.  The scan lane is kind-gated: a ``lax.cond`` runs it only on a
    scan batch.  Its branch only reads the indexes and returns per-lane
    counts, so nothing pool-shaped goes through it and no branch
    materializes a pool-sized copy inside the workload ``lax.scan``.

    The maintenance plane runs as ONE loop before the data op: the §4.2
    rate limit frees this batch's write headroom, the watermark
    hysteresis (armed at every step boundary -- the async job drains the
    previous put's overflow at the next step), and the §5.3 budget for
    read batches.  Then the masked point-op pass, the scan lane, and
    append-only virtual-fill accounting on put batches.
    """
    is_put = op.kind == PUT
    is_get = op.kind == GET
    is_del = op.kind == DELETE
    is_scan = op.kind == SCAN
    ctr0 = state.tier.ctr  # counter baseline for the obs step record
    comp0 = state.comp     # carry baseline for the obs cost deferral

    # ONE pre-op maintenance loop: §4.2 rate limit for this batch's
    # writes, watermark hysteresis (armed at every step boundary: the
    # async job drains the previous put's overflow), §5.3 policy budget
    need = jnp.where(is_put, jnp.sum(op.valid.astype(jnp.int32)), 0)
    state = maintenance(state, cfg, need=need, wm_gate=True,
                        policy_enable=is_get | is_scan, mirror=mirror,
                        force_pin_keys=force_pin_keys)
    # drain one quantum of any in-flight migration right behind the
    # maintenance loop: a trigger step pays one quantum, not the whole
    # job, and keeping the two slow-pool writers adjacent lets XLA chain
    # their in-place updates (no pool-shaped copy per step)
    state = drain_tick(state, cfg)
    before = tiers.free_fast_slots(state.tier)

    # one masked pass for the point lanes, sharing the index lookups
    with jax.named_scope("point_ops"):
        tier, gvals, gfound, gsrc = tiers.apply_point_ops(
            state.tier, cfg.tier, op.keys, op.vals, op.valid,
            is_put=is_put, is_get=is_get, is_del=is_del,
            backend=cfg.backend, interpret=cfg.interpret)
        if cfg.compaction_quantum > 0:
            # dual lookup: gets inside the in-flight range whose rows are
            # not yet drained are served from the un-migrated source
            # slots.  Reads the post-op pools (a GET batch leaves them
            # untouched; op.kind is per-batch) so the pool access chain
            # stays serial.  Drain writes are idempotent bit-equal
            # replays, so draining before vs after this lookup cannot
            # change any get result.
            gvals = compaction.inflight_read(tier, state.comp, op.keys,
                                             gvals, gfound, gsrc)
    # scan lane, kind-gated: only a scan batch runs it.  The branch reads
    # the indexes, fast_ver and tombs and returns per-lane counts alone
    # (nothing pool-shaped goes through it); the counters are charged
    # outside it, zero lanes on any other batch
    scanning = op.valid & is_scan
    lens = jnp.where(scanning, jnp.clip(op.aux, 0, cfg.scan_chunk), 0)

    def lanes():
        with jax.named_scope("scan_lane"):
            return tiers.scan_lane_counts(tier, op.keys, lens,
                                          chunk=cfg.scan_chunk)

    def idle():
        return (jnp.zeros(lens.shape, jnp.int32),
                jnp.zeros(lens.shape + (len(tier.keys),), jnp.int32))

    n_live, per_tier = lax.cond(is_scan, lanes, idle)
    tier = tiers.count_scans(tier, scanning, per_tier)
    state = state._replace(tier=tier)

    if cfg.append_only:
        # versions appended, not updated: in-place updates still consume
        # virtual space until the next merge
        fresh = before - tiers.free_fast_slots(tier)
        state = state._replace(
            virtual_extra=state.virtual_extra
            + jnp.where(is_put, jnp.maximum(need - fresh, 0), 0))

    state = state._replace(steps=state.steps + 1)
    if cfg.consolidate_every > 0:
        state = _consolidation_tick(state, cfg)

    if cfg.obs.enabled:
        # the delta spans the whole step -- maintenance included, so a
        # batch that stalled behind compactions lands in a tail bucket
        delta = obs_plane.counter_delta(state.tier.ctr, ctr0)
        if cfg.compaction_quantum > 0:
            # re-attribute: the cost a trigger step deferred into the
            # carry comes off ITS delta; the quanta this step drained
            # (possibly from earlier triggers) come back on
            delta = compaction.defer_adjust(delta, comp0, state.comp)
        state = state._replace(obs=obs_plane.record_step(
            state.obs, cfg.obs, kind=op.kind,
            n_ops=jnp.sum(op.valid.astype(jnp.int32)), delta=delta))

    b, v = op.vals.shape
    res = OpResult(
        vals=jnp.where(is_get, gvals.astype(jnp.float32),
                       jnp.zeros((b, v), jnp.float32)),
        found=jnp.where(is_get, gfound, is_scan & (n_live > 0)),
        src=jnp.where(is_get, gsrc,
                      jnp.where(is_scan, n_live, -1)).astype(jnp.int32))
    return state, res


def run_ops(state: EngineState, ops: OpBatch, cfg: EngineConfig, *,
            mirror: MirrorFn | None = None,
            force_pin_keys: jax.Array | None = None
            ) -> tuple[EngineState, OpResult]:
    """Drive a whole op stream (OpBatch stacked on a leading axis) through
    ``lax.scan``: N batches, one dispatch.  Results stack likewise."""
    def step(s, op):
        return engine_step(s, op, cfg, mirror=mirror,
                           force_pin_keys=force_pin_keys)

    return lax.scan(step, state, ops)


def _jit(base, cfg: EngineConfig, mirror: MirrorFn | None, donate: bool):
    fn = functools.partial(base, cfg=cfg, mirror=mirror)
    # the program keeps its function's name (``jit_engine_step``) in
    # compiled HLO and profiler traces
    fn.__name__ = base.__name__
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=128)
def _cached_jit(base, cfg: EngineConfig, donate: bool):
    return _jit(base, cfg, None, donate)


def jit_step(cfg: EngineConfig, *, mirror: MirrorFn | None = None,
             donate: bool = True):
    """Jitted ``engine_step`` with the state buffers donated.

    Mirror-less steps are cached per EngineConfig so facade instances with
    the same config share one compilation cache (benchmarks build many)."""
    if mirror is None:
        return _cached_jit(engine_step, cfg, donate)
    return _jit(engine_step, cfg, mirror, donate)


def jit_run_ops(cfg: EngineConfig, *, mirror: MirrorFn | None = None,
                donate: bool = True):
    """Jitted ``run_ops`` with the state buffers donated."""
    if mirror is None:
        return _cached_jit(run_ops, cfg, donate)
    return _jit(run_ops, cfg, mirror, donate)
