"""Device-resident observability state, carried through the fused engine.

``ObsState`` rides inside ``EngineState`` so every metric below is
maintained INSIDE the jitted hot loop -- zero extra dispatches, zero
host syncs; the host only ever reads it back at segment boundaries
(``repro.obs.export``).  Four instruments:

  * ``hist``      -- log2-bucketed histograms of the modeled per-op
                     service cost (Table-1 constants, ``repro.obs.cost``),
                     one row per op kind.  The per-step counter DELTAS --
                     compaction stalls included, which is exactly where
                     the read tail lives -- are turned into a per-op cost
                     and scatter-added branchlessly.  Histograms (not
                     reservoirs) because vmapped per-partition states
                     merge by plain summation.
  * ``timeline``  -- a fixed-size ring of per-step counter deltas
                     (op kind, op count, every ``Counters`` field), the
                     workload-statistics substrate the self-tuning
                     ROADMAP item needs.
  * ``ev_*``      -- a compaction event ring: engine step index, trigger
                     kind (rate-limit / watermark / §5.3 policy), the
                     selected range's MSC score, objects moved and
                     superseded, and the compaction's modeled I/O.
  * burst counters -- ``ev_jobs_t`` (jobs per trigger kind) and
                     ``step_comp_hist`` (steps by the compactions they
                     committed): how bursty compaction is, over every
                     step, where the ring keeps only the last events.

Every update is a masked scatter-add / scatter-set with computed
indices: no ``lax.cond`` over state, so the PR 4 branchless-hot-loop
invariant (``tests/test_hlo_budget.py``) is preserved -- obs arrays are
small and fixed-size, never pool-shaped.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.cost import CostModel, compaction_io_us, step_io_us

if TYPE_CHECKING:
    # repro.core.engine carries ObsState, so this module must not import
    # repro.core at module level (annotations are strings under
    # future-annotations; TIMELINE_FIELDS is resolved lazily below)
    from repro.core.tiers import Counters

# histogram rows: engine op kinds 0..3 (PUT/GET/DELETE/SCAN, matching
# repro.core.engine) plus the serving engine's fused decode tick
TICK = 4
N_KINDS = 5
KIND_NAMES = ("put", "get", "delete", "scan", "tick")

# compaction event trigger kinds (the three gates of engine.maintenance)
TRIG_RATE_LIMIT, TRIG_WATERMARK, TRIG_POLICY = 0, 1, 2
TRIGGER_NAMES = ("rate_limit", "watermark", "policy")

# compaction event-ring entry kinds.  Run-to-completion compactions log a
# single "commit" per job (the legacy shape: ev_count == compactions).
# With ``compaction_quantum > 0`` each job logs a "start" (zero io_us, the
# trigger step) and each subsequent drained quantum a "resume" carrying
# that quantum's io_us; the final quantum's entry is the "commit".
EV_COMMIT, EV_START, EV_RESUME = 0, 1, 2
EVENT_KIND_NAMES = ("commit", "start", "resume")

# per-step compaction-burst histogram: bucket 0 holds steps that committed
# no compaction, bucket b holds [2^(b-1), 2^b), the last everything from
# 256 (engine.max_rounds) up
STEP_COMP_BUCKETS = 10

# timeline row layout: [kind, n_ops, *flattened Counters deltas] --
# per-tier vector counters expand to one column per entry ("hits0",
# "hits1", ...).  Resolved lazily (module __getattr__) so importing
# repro.obs does not pull in repro.core before repro.core.engine has
# finished importing US.
def timeline_fields(n_tiers: int = 2) -> tuple:
    from repro.core.tiers import Counters
    zeros = Counters.zeros(n_tiers)
    out = ["kind", "n_ops"]
    for f in Counters._fields:
        leaf = getattr(zeros, f)
        if leaf.ndim == 0:
            out.append(f)
        else:
            out.extend(f"{f}{i}" for i in range(leaf.shape[0]))
    return tuple(out)


def _timeline_fields() -> tuple:
    return timeline_fields(2)


def __getattr__(name: str):
    if name == "TIMELINE_FIELDS":
        globals()[name] = _timeline_fields()
        return globals()[name]
    raise AttributeError(name)


class ObsConfig(NamedTuple):
    """Static observability knobs (closure constants under jit; hashable
    so they key the engine's jit caches through ``EngineConfig``)."""
    enabled: bool = True
    n_buckets: int = 32        # log2 latency buckets: bucket b covers
                               # (2^(b-1), 2^b] us, bucket 0 covers <= 1us
    timeline_len: int = 256    # per-step counter-delta ring entries
    event_len: int = 128       # compaction event ring entries
    cost: CostModel = CostModel()
    fast_write_amp: float = 1.0  # LSM baselines model NVM-internal
                               # rewrites (harness.FAST_WRITE_AMP)
    n_tiers: int = 2           # sizes the timeline row + per-boundary
                               # job counters; facades keep it in sync
                               # with TierConfig.n_tiers

    @property
    def n_boundaries(self) -> int:
        return self.n_tiers - 1


class ObsState(NamedTuple):
    """One donatable pytree of small fixed-size instruments."""
    hist: jax.Array          # i32[N_KINDS, n_buckets] per-op-cost histogram
    timeline: jax.Array      # i32[timeline_len, len(TIMELINE_FIELDS)]
    t_pos: jax.Array         # i32: total steps recorded (ring wraps)
    ev_step: jax.Array       # i32[event_len] engine step index
    ev_trigger: jax.Array    # i32[event_len] TRIG_* kind
    ev_score: jax.Array      # f32[event_len] selected MSC score
    ev_moved: jax.Array      # i32[event_len] demoted + promoted + merged
    ev_superseded: jax.Array # i32[event_len] stale copies merged away
    ev_io_us: jax.Array      # f32[event_len] modeled compaction I/O
    ev_count: jax.Array      # i32: total events recorded (ring wraps)
    # trailing fields (appended, defaulted nowhere -- init() builds them;
    # vmapped merge-by-summation and donation treat them like the rest):
    hist_sum: jax.Array      # f32[N_KINDS, n_buckets] per-bucket cost SUM
                             # (mean = hist_sum / hist: sub-bucket percentile
                             # interpolation, repro.obs.export)
    ev_kind: jax.Array       # i32[event_len] EV_* entry kind
    ev_jobs: jax.Array       # i32: compaction JOBS recorded (one per
                             # trigger; == ev_count when quantum is off)
    ev_boundary: jax.Array   # i32[event_len] tier boundary of the event
                             # (0 = slab/run boundary, the legacy pair)
    ev_jobs_b: jax.Array     # i32[n_boundaries] jobs per boundary
                             # (sums to ev_jobs; conservation oracle:
                             # ev_jobs_b[b] == ctr.comp_by_boundary[b])
    ev_jobs_t: jax.Array     # i32[3] jobs per TRIG_* kind (sums to
                             # ev_jobs): how much of compaction the
                             # watermark hysteresis runs
    step_comp_hist: jax.Array  # i32[STEP_COMP_BUCKETS] engine steps by
                             # the compactions committed in the step
                             # (sums to t_pos): the burst a tail step ran


def init(cfg: ObsConfig) -> ObsState:
    e = cfg.event_len
    return ObsState(
        hist=jnp.zeros((N_KINDS, cfg.n_buckets), jnp.int32),
        timeline=jnp.zeros(
            (cfg.timeline_len, len(timeline_fields(cfg.n_tiers))),
            jnp.int32),
        t_pos=jnp.zeros((), jnp.int32),
        ev_step=jnp.zeros((e,), jnp.int32),
        ev_trigger=jnp.zeros((e,), jnp.int32),
        ev_score=jnp.zeros((e,), jnp.float32),
        ev_moved=jnp.zeros((e,), jnp.int32),
        ev_superseded=jnp.zeros((e,), jnp.int32),
        ev_io_us=jnp.zeros((e,), jnp.float32),
        ev_count=jnp.zeros((), jnp.int32),
        hist_sum=jnp.zeros((N_KINDS, cfg.n_buckets), jnp.float32),
        ev_kind=jnp.zeros((e,), jnp.int32),
        ev_jobs=jnp.zeros((), jnp.int32),
        ev_boundary=jnp.zeros((e,), jnp.int32),
        ev_jobs_b=jnp.zeros((cfg.n_boundaries,), jnp.int32),
        ev_jobs_t=jnp.zeros((len(TRIGGER_NAMES),), jnp.int32),
        step_comp_hist=jnp.zeros((STEP_COMP_BUCKETS,), jnp.int32),
    )


def bucket_of_us(us: jax.Array, n_buckets: int) -> jax.Array:
    """Log2 bucket index of a (scalar or vector) cost in microseconds:
    bucket 0 holds us <= 1, bucket b holds (2^(b-1), 2^b].  Mirrored
    bit-for-bit by ``repro.obs.export.bucket_of_us_np`` (the oracle).

    ceil(log2(x)) is read off the f32 bit pattern (exponent field, plus
    one unless the mantissa is zero, i.e. x is an exact power of two):
    pure integer ops, so the host mirror and every backend agree on ALL
    inputs -- libm log2 implementations differ by a ULP right above
    bucket boundaries, which ceil() would amplify into a bucket flip."""
    us = jnp.maximum(jnp.asarray(us, jnp.float32), jnp.float32(1e-6))
    bits = jax.lax.bitcast_convert_type(us, jnp.int32)
    b = (bits >> 23) - 127 + ((bits & 0x7FFFFF) != 0).astype(jnp.int32)
    return jnp.clip(b, 0, n_buckets - 1)


def counter_delta(after: Counters, before: Counters) -> Counters:
    return jax.tree.map(lambda a, b: a - b, after, before)


def step_comp_bucket(n: jax.Array) -> jax.Array:
    """``step_comp_hist`` bucket of a step that committed ``n >= 0``
    compactions: n's bit length, so 0 -> 0, 1 -> 1, 2-3 -> 2, ...,
    capped at the last bucket."""
    n = jnp.asarray(n, jnp.int32)
    return jnp.minimum(32 - jax.lax.clz(n), STEP_COMP_BUCKETS - 1)


@jax.named_scope("obs_record")
def record_step(obs: ObsState, cfg: ObsConfig, *, kind: jax.Array,
                n_ops: jax.Array, delta: Counters) -> ObsState:
    """Fold one engine step's counter deltas into the histograms and the
    timeline ring.  ``kind`` is a traced scalar (the branchless engine
    passes ``op.kind`` straight through); the modeled step cost INCLUDES
    any compaction I/O the step's maintenance plane performed -- a batch
    that stalled behind a compaction lands in a high bucket, which is
    the tail the paper's headline claim is about.

    Branchless: one scatter-add into ``hist[kind, bucket]`` weighted by
    the batch's valid-op count, one into ``step_comp_hist`` at the
    step's compaction count, one scatter-set of the timeline row."""
    n_ops = jnp.asarray(n_ops, jnp.int32)
    us = step_io_us(delta, cfg.cost, cfg.fast_write_amp)
    per_op = us / jnp.maximum(n_ops.astype(jnp.float32), 1.0)
    b = bucket_of_us(per_op, cfg.n_buckets)
    hist = obs.hist.at[kind, b].add(n_ops)
    hist_sum = obs.hist_sum.at[kind, b].add(
        per_op * n_ops.astype(jnp.float32))
    row = jnp.concatenate(
        [jnp.stack([jnp.asarray(kind, jnp.int32), n_ops])]
        + [jnp.atleast_1d(jnp.asarray(v, jnp.int32)) for v in delta])
    timeline = obs.timeline.at[obs.t_pos % cfg.timeline_len].set(row)
    step_comp_hist = obs.step_comp_hist.at[
        step_comp_bucket(delta.compactions)].add(1)
    return obs._replace(hist=hist, hist_sum=hist_sum, timeline=timeline,
                        t_pos=obs.t_pos + 1, step_comp_hist=step_comp_hist)


@jax.named_scope("obs_record")
def record_compaction(obs: ObsState, cfg: ObsConfig, *, step: jax.Array,
                      trigger: jax.Array,
                      stats: "CompactionStats",  # noqa: F821
                      kind: int = EV_COMMIT, new_job: bool = True,
                      io_us: jax.Array | None = None,
                      boundary: int = 0) -> ObsState:
    """Append one compaction to the event ring (runs INSIDE the
    ``engine.maintenance`` while_loop body -- all scatter-sets, the ring
    index is ``ev_count % event_len``).

    Run-to-completion keeps the defaults: one EV_COMMIT per job pricing
    the whole migration.  The quantized path logs the trigger as an
    EV_START with ``io_us=0.0`` (the step defers its migration cost into
    the in-flight carry); ``new_job`` counts jobs (``ev_jobs``, and by
    trigger kind ``ev_jobs_t``) independently of ring entries."""
    i = obs.ev_count % cfg.event_len
    moved = stats.n_demoted + stats.n_promoted + stats.n_merged
    if io_us is None:
        io_us = compaction_io_us(stats, cfg.cost, cfg.fast_write_amp,
                                 boundary=boundary)
    return obs._replace(
        ev_step=obs.ev_step.at[i].set(jnp.asarray(step, jnp.int32)),
        ev_trigger=obs.ev_trigger.at[i].set(
            jnp.asarray(trigger, jnp.int32)),
        ev_score=obs.ev_score.at[i].set(
            jnp.asarray(stats.score, jnp.float32)),
        ev_moved=obs.ev_moved.at[i].set(moved.astype(jnp.int32)),
        ev_superseded=obs.ev_superseded.at[i].set(
            stats.n_superseded.astype(jnp.int32)),
        ev_io_us=obs.ev_io_us.at[i].set(jnp.asarray(io_us, jnp.float32)),
        ev_kind=obs.ev_kind.at[i].set(jnp.int32(kind)),
        ev_boundary=obs.ev_boundary.at[i].set(jnp.int32(boundary)),
        ev_count=obs.ev_count + 1,
        ev_jobs=obs.ev_jobs + (1 if new_job else 0),
        ev_jobs_b=obs.ev_jobs_b.at[boundary].add(1 if new_job else 0),
        ev_jobs_t=obs.ev_jobs_t.at[trigger].add(1 if new_job else 0))


@jax.named_scope("obs_record")
def record_drain(obs: ObsState, cfg: ObsConfig, *, step: jax.Array,
                 trigger: jax.Array, score: jax.Array, moved: jax.Array,
                 io_us: jax.Array, done: jax.Array) -> ObsState:
    """Append one drained compaction quantum to the event ring: EV_RESUME
    while the job still has backlog, EV_COMMIT on the quantum that
    finishes it.  Branchless masked ring write -- when ``moved == 0``
    (nothing in flight this step) the scatter index is parked past the
    ring (``mode="drop"``) and ``ev_count`` does not advance, so
    drain-free steps leave the ring untouched bit-for-bit."""
    write = moved > 0
    i = jnp.where(write, obs.ev_count % cfg.event_len, cfg.event_len)
    kind = jnp.where(done, jnp.int32(EV_COMMIT), jnp.int32(EV_RESUME))
    at = lambda a: a.at[i]
    return obs._replace(
        ev_step=at(obs.ev_step).set(jnp.asarray(step, jnp.int32),
                                    mode="drop"),
        ev_trigger=at(obs.ev_trigger).set(
            jnp.asarray(trigger, jnp.int32), mode="drop"),
        ev_score=at(obs.ev_score).set(
            jnp.asarray(score, jnp.float32), mode="drop"),
        ev_moved=at(obs.ev_moved).set(moved.astype(jnp.int32),
                                      mode="drop"),
        ev_superseded=at(obs.ev_superseded).set(jnp.int32(0),
                                                mode="drop"),
        ev_io_us=at(obs.ev_io_us).set(jnp.asarray(io_us, jnp.float32),
                                      mode="drop"),
        ev_kind=at(obs.ev_kind).set(kind, mode="drop"),
        ev_boundary=at(obs.ev_boundary).set(jnp.int32(0), mode="drop"),
        ev_count=obs.ev_count + write.astype(jnp.int32))
