"""Multi-tiered Storage Compaction metric (PrismDB §5, Eq. 1).

    MSC = benefit / cost
    benefit = sum_j coldness(j)            coldness = 1 / (clock_j + 1)
    cost    = F * (2 - o) / (1 - p) + 1

  F = t_f / t_n   fanout: slow-tier objects per fast-tier object in range
  p               fraction of fast-tier objects in range that are pinned
  o               fraction of slow-tier run objects superseded by the range

Two implementations, exactly as in the paper:

  * ``precise_score``  -- walks every object in the candidate range (tracker
    lookups + index probes).  4x less slow-tier write I/O than an LSM
    baseline but CPU-bound: long compactions (paper Fig. 6).
  * ``approx_score``   -- weighted average of per-bucket (p, o, F) statistics
    maintained incrementally; same I/O, ~15x cheaper to evaluate.

Candidate ranges are whole-run windows (``i`` consecutive runs, default 1) or
bucket-aligned synthetic ranges at bootstrap; power-of-k sampling (§A.1,
k = 8 default) picks the candidates to score.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import mapper, tracker
from repro.core.tiers import TierConfig, TierState, bucket_of
from repro.core.utils import PADKEY, segment_in_range, sorted_lookup


class Candidate(NamedTuple):
    lo: jax.Array          # i32[k]
    hi: jax.Array          # i32[k]
    run_start: jax.Array   # i32[k] first run id of window (-1 = synthetic)
    run_span: jax.Array    # i32[k] number of runs in window
    t_f: jax.Array         # i32[k] slow objects in window


def bucket_clock_hist(state: TierState, cfg: TierConfig) -> jax.Array:
    """int32[B, 4]: clock histogram of *tracked fast-tier* keys per bucket.

    Recomputed per compaction round (O(T)) -- the approx-MSC
    benefit/popularity estimate reads from this.  Counted by sorting the
    (bucket, clock) codes and searching the code boundaries: on the TPU a
    bincount (a scatter-add) of the tracker costs more than a sort of it.
    """
    trk = state.tracker
    ok = (trk.keys >= 0) & (trk.loc == tracker.LOC_FAST)
    b = bucket_of(cfg, jnp.maximum(trk.keys, 0))
    nc = cfg.n_buckets * 4
    code = jnp.where(ok, b * 4 + trk.clock.astype(jnp.int32), nc)
    edges = jnp.searchsorted(jnp.sort(code), jnp.arange(nc + 1))
    return (edges[1:] - edges[:-1]).reshape(cfg.n_buckets, 4) \
        .astype(jnp.int32)


# -------------------------------------------------------------- candidates

def candidate_ranges(state: TierState, cfg: TierConfig,
                     rng: jax.Array) -> Candidate:
    """Power-of-k candidate windows (k = cfg.power_k).

    With active runs, the key space is partitioned into *ownership ranges*:
    run j (in lo-order) owns ``[run_lo_j, run_lo_{j+1})`` -- the first run
    additionally owns ``[0, run_lo_0)`` and the last owns up to key_space.
    This guarantees every fast-tier key falls in exactly one candidate (the
    paper's "NVM key space divided by SST file bounds") while keeping runs
    disjoint.  A candidate window is ``i`` consecutive ownership ranges.

    Bootstrap (no runs): bucket-aligned synthetic ranges sized to ~run_size
    expected fast keys.
    """
    k, r = cfg.power_k, cfg.max_runs
    n_active = jnp.sum(state.run_active.astype(jnp.int32))

    # --- run-window candidates: order active runs by lo
    lo_key = jnp.where(state.run_active, state.run_lo, PADKEY)
    order = jnp.argsort(lo_key)            # active runs first, by lo
    pos = jax.random.randint(rng, (k,), 0, jnp.maximum(n_active, 1))
    span = jnp.minimum(jnp.int32(cfg.range_fanout_i),
                       jnp.maximum(n_active, 1))
    pos = jnp.minimum(pos, jnp.maximum(n_active - span, 0))
    first = order[jnp.clip(pos, 0, r - 1)]
    # ownership bounds in lo-order
    ordered_lo = lo_key[order]
    own_lo_all = jnp.where(jnp.arange(r) == 0, 0, ordered_lo)
    nxt = jnp.concatenate([ordered_lo[1:], jnp.array([PADKEY], jnp.int32)])
    own_hi_all = jnp.where(jnp.arange(r) == n_active - 1, cfg.key_space,
                           jnp.minimum(nxt, cfg.key_space))
    lo_run = own_lo_all[jnp.clip(pos, 0, r - 1)]
    hi_run = own_hi_all[jnp.clip(pos + span - 1, 0, r - 1)]
    # t_f = sum of counts of runs in window
    win = (jnp.arange(r)[None, :] >= pos[:, None]) & \
          (jnp.arange(r)[None, :] < (pos + span)[:, None])
    counts_by_order = state.run_count[order]
    tf_run = jnp.sum(jnp.where(win, counts_by_order[None, :], 0), axis=1)

    # --- synthetic candidates (bootstrap)
    b_width = max(cfg.key_space // cfg.n_buckets, 1)
    total_fast = jnp.maximum(jnp.sum(state.bucket_fast), 1)
    per_bucket = total_fast / cfg.n_buckets
    span_b = jnp.clip((cfg.run_size / jnp.maximum(per_bucket, 1e-6))
                      .astype(jnp.int32), 1, cfg.n_buckets)
    start_b = jax.random.randint(jax.random.fold_in(rng, 1), (k,), 0,
                                 cfg.n_buckets)
    start_b = jnp.minimum(start_b, jnp.maximum(cfg.n_buckets - span_b, 0))
    lo_syn = start_b * b_width
    hi_syn = jnp.minimum((start_b + span_b) * b_width, cfg.key_space)

    use_runs = n_active > 0
    return Candidate(
        lo=jnp.where(use_runs, lo_run, lo_syn).astype(jnp.int32),
        hi=jnp.where(use_runs, hi_run, hi_syn).astype(jnp.int32),
        run_start=jnp.where(use_runs, first.astype(jnp.int32), -1),
        run_span=jnp.where(use_runs, span, 0)
        * jnp.ones((k,), jnp.int32),
        t_f=jnp.where(use_runs, tf_run, 0).astype(jnp.int32),
    )


# ------------------------------------------------------------ precise MSC

def precise_score(state: TierState, cfg: TierConfig, lo: jax.Array,
                  hi: jax.Array, t_f: jax.Array, probs: jax.Array,
                  cap_fast: int, cap_slow: int) -> jax.Array:
    """Exact Eq. 1 for one range: per-object tracker + index walks."""
    pos, m = segment_in_range(state.fidx_keys, lo, hi, cap_fast)
    fkeys = jnp.where(m, state.fidx_keys[pos], PADKEY)
    clock, tracked = tracker.lookup_clock(state.tracker, fkeys)
    cold = jnp.where(m, mapper.coldness_from_clock(clock, tracked), 0.0)
    benefit = jnp.sum(cold)
    # exact t_n (not capped) from index positions
    t_n = (jnp.searchsorted(state.fidx_keys, hi)
           - jnp.searchsorted(state.fidx_keys, lo)).astype(jnp.float32)
    pin_p = jnp.where(m, probs[jnp.clip(clock.astype(jnp.int32), 0, 3)]
                      * tracked, 0.0)
    p = jnp.sum(pin_p) / jnp.maximum(jnp.sum(m.astype(jnp.float32)), 1.0)
    # o: walk the slow objects in range, probe the fast index (CPU-heavy!)
    spos, sm = segment_in_range(state.sidx_keys, lo, hi, cap_slow)
    skeys = jnp.where(sm, state.sidx_keys[spos], PADKEY)
    _, in_fast = sorted_lookup(state.fidx_keys, state.fidx_slots, skeys)
    o = jnp.sum((in_fast & sm).astype(jnp.float32)) / \
        jnp.maximum(t_f.astype(jnp.float32), 1.0)
    return _msc(benefit, t_n, t_f.astype(jnp.float32), p, o)


# ------------------------------------------------------------- approx MSC

def approx_score(state: TierState, cfg: TierConfig, lo: jax.Array,
                 hi: jax.Array, t_f: jax.Array,
                 bhist: jax.Array, probs: jax.Array) -> jax.Array:
    """Eq. 1 from bucket statistics: weighted average over overlapped buckets.

    ``bhist`` is bucket_clock_hist(state, cfg); bucket_fast/slow/overlap come
    from the incrementally-maintained TierState fields.
    """
    b_width = max(cfg.key_space // cfg.n_buckets, 1)
    edges_lo = jnp.arange(cfg.n_buckets, dtype=jnp.int32) * b_width
    edges_hi = edges_lo + b_width
    # fractional coverage of each bucket by [lo, hi)
    inter = (jnp.minimum(edges_hi, hi) - jnp.maximum(edges_lo, lo)) \
        .astype(jnp.float32)
    w = jnp.clip(inter / float(b_width), 0.0, 1.0)        # [B]

    nf = state.bucket_fast.astype(jnp.float32)
    ns = state.bucket_slow.astype(jnp.float32)
    ov = state.bucket_overlap.astype(jnp.float32)
    h = [bhist[:, c].astype(jnp.float32) for c in range(4)]   # [B] each
    untracked = jnp.maximum(nf - (((h[0] + h[1]) + h[2]) + h[3]), 0.0)
    # the clock-class sums are spelled out in a fixed order (no matvec):
    # a TPU matmul would round them through bf16, and the msc_score
    # kernel evaluates exactly these products and sums
    coldness = (((h[0] * 1.0 + h[1] * 0.5) + h[2] * (1.0 / 3.0))
                + h[3] * 0.25)
    pin = (((h[0] * probs[0] + h[1] * probs[1]) + h[2] * probs[2])
           + h[3] * probs[3])
    benefit = jnp.sum(w * (coldness + untracked))
    t_n = jnp.sum(w * nf)
    pinned = jnp.sum(w * pin)
    p = pinned / jnp.maximum(t_n, 1.0)
    tf_est = jnp.maximum(jnp.sum(w * ns), t_f.astype(jnp.float32))
    o = jnp.sum(w * ov) / jnp.maximum(tf_est, 1.0)
    return _msc(benefit, t_n, tf_est, p, o)


def _msc(benefit, t_n, t_f, p, o):
    p = jnp.clip(p, 0.0, 0.999)          # p -> 1 means nothing to demote
    o = jnp.clip(o, 0.0, 1.0)
    f = t_f / jnp.maximum(t_n, 1.0)
    cost = f * (2.0 - o) / (1.0 - p) + 1.0
    return jnp.where(t_n > 0, benefit / cost, 0.0)


# --------------------------------------------------------------- selection

def min_overlap_score(state: TierState, cfg: TierConfig, lo: jax.Array,
                      hi: jax.Array, t_f: jax.Array) -> jax.Array:
    """RocksDB's kMinOverlappingRatio analogue: prefer the range with the
    least slow-tier merge work per fast-tier byte (no popularity term).
    Used by the LSM / read-aware baselines (paper §3, §5.3 Fig. 6)."""
    t_n = (jnp.searchsorted(state.fidx_keys, hi)
           - jnp.searchsorted(state.fidx_keys, lo)).astype(jnp.float32)
    f = t_f.astype(jnp.float32) / jnp.maximum(t_n, 1.0)
    return jnp.where(t_n > 0, 1.0 / (f + 1.0), 0.0)


def select_range(state: TierState, cfg: TierConfig, rng: jax.Array,
                 precise: bool = False,
                 cap_fast: int | None = None,
                 cap_slow: int | None = None,
                 selection: str = "msc",
                 backend: str = "reference",
                 interpret: bool | None = None) -> tuple[Candidate,
                                                         jax.Array,
                                                         jax.Array]:
    """Score k power-of-k candidates, return (candidates, scores, best_idx).

    selection: "msc" (the paper's metric) or "min_overlap" (LSM baseline).
    ``backend`` statically routes the approx-MSC scoring (the every-
    compaction-tick primitive, paper Fig. 6) through the Pallas msc_score
    kernel; precise and min_overlap scoring are not kernelized (the paper
    only optimizes the approximate path).
    """
    cand = candidate_ranges(state, cfg, rng)
    hist = tracker.clock_histogram(state.tracker)
    probs = mapper.pin_probabilities(hist, jnp.float32(cfg.pin_threshold))
    if selection == "min_overlap":
        scores = jax.vmap(
            lambda lo, hi, tf: min_overlap_score(state, cfg, lo, hi, tf))(
                cand.lo, cand.hi, cand.t_f)
    elif precise:
        cf = cap_fast or 2 * cfg.run_size
        cs = cap_slow or 2 * cfg.run_size * max(cfg.range_fanout_i, 1)
        scores = jax.vmap(
            lambda lo, hi, tf: precise_score(state, cfg, lo, hi, tf, probs,
                                             cf, cs))(cand.lo, cand.hi,
                                                      cand.t_f)
    elif backend != "reference":
        from repro.kernels.msc_score.ops import score_candidates
        bhist = bucket_clock_hist(state, cfg)
        scores = score_candidates(
            cand.lo, cand.hi, cand.t_f, state.bucket_fast, state.bucket_slow,
            state.bucket_overlap, bhist, probs,
            bucket_width=max(cfg.key_space // cfg.n_buckets, 1),
            backend=backend, interpret=interpret)
    else:
        bhist = bucket_clock_hist(state, cfg)
        scores = jax.vmap(
            lambda lo, hi, tf: approx_score(state, cfg, lo, hi, tf, bhist,
                                            probs))(cand.lo, cand.hi,
                                                    cand.t_f)
    return cand, scores, jnp.argmax(scores)


# ------------------------------------------------- deep-boundary selection

def select_boundary_run(state: TierState, cfg: TierConfig, boundary: int,
                        cost=None) -> tuple:
    """Pick the tier-``boundary`` run to migrate down across the
    ``boundary`` -> ``boundary + 1`` boundary (deep boundaries only,
    ``boundary >= 1``).

    Eq. 1's popularity terms do not exist below the slab tier (the clock
    tracker observes tier-0 accesses), so the deep score degenerates to
    MSC's benefit/cost core priced with THIS boundary's coefficients:

        score_j = rows_freed_j / (io_us_j + 1)
        io_us_j = t_u * seq_read(up) + t_l * seq_read(lo)
                  + (t_u + t_l) * seq_write(lo)

    where ``t_l`` sums the counts of every lower run overlapping run j's
    range.  Returns ``(rid, lo, hi, score, overlap_mask)`` with
    ``overlap_mask`` a bool[max_runs] over the LOWER tier's directory.
    """
    from repro.obs.cost import CostModel
    cost = cost if cost is not None else CostModel()
    du, dl = boundary - 1, boundary
    up_lo, up_hi = state.dir_lo[du], state.dir_hi[du]
    up_cnt, up_act = state.dir_count[du], state.dir_active[du]
    lo_lo, lo_hi = state.dir_lo[dl], state.dir_hi[dl]
    lo_cnt, lo_act = state.dir_count[dl], state.dir_active[dl]
    # [U, L] overlap of upper run u's range with lower run l's range
    ov = (lo_act[None, :]
          & (lo_lo[None, :] < up_hi[:, None])
          & (lo_hi[None, :] > up_lo[:, None]))
    t_l = jnp.sum(jnp.where(ov, lo_cnt[None, :], 0), axis=1) \
        .astype(jnp.float32)
    t_u = up_cnt.astype(jnp.float32)
    cu, cl = cost.tier(boundary), cost.tier(boundary + 1)
    io = (t_u * cu.seq_read_us_per_obj + t_l * cl.seq_read_us_per_obj
          + (t_u + t_l) * cl.seq_write_us_per_obj)
    score = jnp.where(up_act & (up_cnt > 0), t_u / (io + 1.0), -jnp.inf)
    rid = jnp.argmax(score).astype(jnp.int32)
    return (rid, up_lo[rid], up_hi[rid], score[rid], ov[rid])
