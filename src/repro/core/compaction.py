"""Compaction engine (PrismDB §4.2, §5.3, §6).

One compaction:
  1. select a key range with power-of-k + MSC (precise or approx);
  2. read the range's fast-tier objects; pin the popular ones (mapper),
     demote the rest (tombstones always demote = delete the slow copy);
  3. read the overlapping slow-tier run window (whole runs: sequential I/O);
     drop run objects superseded by *any* live fast copy (stale cleaning);
  4. optionally promote hot run objects to the fast tier (paper: promotion
     piggybacks on the read the compaction already paid for);
  5. merge-sort survivors + demotions into a fresh run (append to the log),
     free the old runs' slots and the demoted fast slots, rebuild indices,
     new Bloom filter, update tracker location bits + bucket stats.

Everything static-shape; ``cap_fast``/``cap_slow`` bound the per-compaction
working set exactly like the paper bounds compaction size by SST file bounds.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bloom, mapper, msc, tracker
from repro.core.tiers import (Counters, TierConfig, TierState, bucket_of,
                              fast_occupancy, run_of_keys)
from repro.core.utils import (PADKEY, alloc_slots, merge_index_update,
                              segment_in_range, sorted_lookup,
                              splice_index_range)


class Movement(NamedTuple):
    """Physical data movement of one compaction, for payload mirrors.

    The core tracks keys/placement; payload arrays (KV pages, embedding
    rows) live outside and replay these moves (the tier_compact kernel's
    job on TPU).  All arrays static-size, masked by *_valid.

    ``boundary`` names the adjacent-tier boundary the movement crosses:
    ``m_src_tier`` values are then the boundary's upper (== boundary) or
    lower (== boundary + 1) tier index, and destinations live in the
    lower tier.  Boundary 0 keeps the historical 0=fast / 1=slow
    encoding.  The kernels still see plain (src, dst) pool pairs -- the
    ``kernels.tier_compact.ops`` wrapper selects the boundary's pools.
    """
    m_src_tier: jax.Array   # i32[cap_f+cap_s] source tier per merged write
    m_src_slot: jax.Array   # i32[cap_f+cap_s] source slot in its tier
    m_dst_slot: jax.Array   # i32[cap_f+cap_s] destination lower-tier slot
    m_valid: jax.Array      # bool
    p_src_slot: jax.Array   # i32[cap_s] promotion source (lower tier)
    p_dst_slot: jax.Array   # i32[cap_s] promotion destination (upper tier)
    p_valid: jax.Array      # bool
    m_key: jax.Array = ()   # i32[cap_f+cap_s] merged keys, sorted (PADKEY
                            # pad) -- the in-flight carry's lookup key for
                            # dual reads against a half-migrated range
    boundary: jax.Array = ()  # i32 scalar: which adjacent-tier boundary


class CompactionStats(NamedTuple):
    selected_lo: jax.Array
    selected_hi: jax.Array
    score: jax.Array
    n_demoted: jax.Array
    n_promoted: jax.Array
    n_merged: jax.Array
    n_superseded: jax.Array    # stale slow copies merged away (duplicates)
    n_run_read: jax.Array      # slow objects read (whole window, seq I/O)
    n_run_written: jax.Array   # slow objects written (new runs, seq I/O)


def sub_runs(mvalid: jax.Array, n_merged: jax.Array,
             run_size: int) -> tuple[jax.Array, int]:
    """Split a merge's sorted output into sub-runs of [run_size,
    2*run_size) rows (one smaller run when fewer than run_size rows
    merged).  Returns ``(sub_of, n_sub)``: the sub-run of each row
    (``n_sub - 1`` on invalid rows) and the static sub-run count.

    The remainder joins the last full sub-run instead of becoming a run
    of its own: a split into full runs plus a small tail leaves behind
    runs whose ownership range no later write lands in, which pile up
    until the run directory is full -- and a merge that finds no free
    directory entry loses rows.  Every run stays below 2*run_size, the
    per-run share of each window cap, so a later window reads it whole.
    """
    n_sub = max(mvalid.shape[0] // run_size, 1) + 1
    rank = jnp.cumsum(mvalid.astype(jnp.int32)) - 1       # rank among valid
    n_full = jnp.maximum(n_merged // run_size, 1)
    sub_of = jnp.where(mvalid, jnp.minimum(rank // run_size, n_full - 1),
                       n_sub - 1).astype(jnp.int32)
    return sub_of, n_sub


def compact_once(state: TierState, cfg: TierConfig, rng: jax.Array,
                 promote: bool = True, precise: bool = False,
                 cap_fast: int | None = None,
                 cap_slow: int | None = None,
                 with_movement: bool = False,
                 force_pin_keys: jax.Array | None = None,
                 selection: str = "msc",
                 pin_mode: str = "object",
                 backend: str = "reference",
                 interpret: bool | None = None):
    """One compaction.

    ``force_pin_keys``: optional sorted int32 array of keys that must never
    demote (e.g. a paged-KV sequence's mutable tail page, or rows dirtied by
    the current optimizer step).  The paper's analogue is the memtable /
    in-flight version check done under the partition lock (§6).

    Baseline knobs (benchmarks, paper §3/§7):
      selection: "msc" | "min_overlap" (RocksDB kMinOverlappingRatio)
      pin_mode:  "object" (PrismDB) | "none" (LSM: demote everything) |
                 "file" (Mutant: whole-range all-or-nothing placement)

    ``backend``/``interpret`` statically route the approx-MSC candidate
    scoring through the Pallas msc_score kernel (see ``msc.select_range``);
    the Movement data plane itself is replayed by the payload MIRRORS,
    which take the same knobs (tier_compact kernel).
    """
    cap_fast = cap_fast or 2 * cfg.run_size
    cap_slow = cap_slow or 2 * cfg.run_size * max(cfg.range_fanout_i, 1)
    r_sel, r_pin, r_pro = jax.random.split(rng, 3)

    with jax.named_scope("select"):
        cand, scores, best = msc.select_range(
            state, cfg, r_sel, precise=precise, cap_fast=cap_fast,
            cap_slow=cap_slow, selection=selection, backend=backend,
            interpret=interpret)
        lo, hi = cand.lo[best], cand.hi[best]
        run_start, run_span = cand.run_start[best], cand.run_span[best]
        # A window reads at most cap_slow slow rows.  Runs stay below that
        # (``sub_runs``), but rows a merge wrote when the run directory had
        # no free entry can push a key range past it: the window then ends
        # at the first row it cannot read, so that the merged rows splice
        # back into the slow index in order.
        s_lo = jnp.searchsorted(state.sidx_keys, lo)
        over = jnp.searchsorted(state.sidx_keys, hi) - s_lo > cap_slow
        hi = jnp.where(over, state.sidx_keys[jnp.minimum(
            s_lo + cap_slow, state.sidx_keys.shape[0] - 1)], hi)

    with jax.named_scope("demote"):
        hist = tracker.clock_histogram(state.tracker)
        # capacity guard (beyond-paper; the paper defers threshold tuning
        # to future work): the pin budget must leave headroom below fast
        # capacity, else compactions cannot free space and the system
        # death-spirals when tracked_keys * threshold > fast_slots (e.g. a
        # 5% fast tier).
        tracked_total = jnp.maximum(jnp.sum(hist).astype(jnp.float32), 1.0)
        cap_frac = 0.6 * cfg.fast_slots / tracked_total
        threshold = jnp.minimum(jnp.float32(cfg.pin_threshold), cap_frac)
        probs = mapper.pin_probabilities(hist, threshold)

        # ---- fast-tier range: pin or demote --------------------------------
        fpos, fm = segment_in_range(state.fidx_keys, lo, hi, cap_fast)
        fkeys = jnp.where(fm, state.fidx_keys[fpos], PADKEY)
        fslots = jnp.where(fm, state.fidx_slots[fpos], 0)
        tomb = state.fast_ver[fslots] < 0
        clock, tracked = tracker.lookup_clock(state.tracker, fkeys)
        if pin_mode == "none":
            pinned = jnp.zeros_like(fm)
        elif pin_mode == "file":
            # Mutant-style file granularity: the whole range stays hot iff
            # its average pin probability crosses 1/2 (single placement
            # decision per file -- the coarseness the paper criticizes in
            # §7.1).
            per_obj = probs[jnp.clip(clock.astype(jnp.int32), 0, 3)] \
                * tracked.astype(jnp.float32)
            avg = jnp.sum(jnp.where(fm, per_obj, 0.0)) \
                / jnp.maximum(jnp.sum(fm.astype(jnp.float32)), 1.0)
            pinned = fm & ~tomb & (avg >= 0.5)
        else:
            pinned = mapper.pin_decisions(clock, tracked, probs, r_pin) \
                & fm & ~tomb
        if force_pin_keys is not None:
            pos_f = jnp.clip(jnp.searchsorted(force_pin_keys, fkeys), 0,
                             force_pin_keys.shape[0] - 1)
            forced = force_pin_keys[pos_f] == fkeys
            pinned = pinned | (forced & fm & ~tomb)
        demote = fm & ~pinned          # tombstones always leave fast tier
        demote_data = demote & ~tomb   # tombstones carry no payload

        # ---- slow-tier window ----------------------------------------------
        spos, sm = segment_in_range(state.sidx_keys, lo, hi, cap_slow)
        skeys = jnp.where(sm, state.sidx_keys[spos], PADKEY)
        sslots = jnp.where(sm, state.sidx_slots[spos], 0)
        _, in_fast = sorted_lookup(state.fidx_keys, state.fidx_slots, skeys)
        superseded = in_fast & sm      # any live fast copy (or tombstone)

        # ---- free demoted fast slots, then install promotions ---------------
        # Promotions (paper §4.2): the compaction already paid the run
        # read, so hot slow-tier objects may ride back to the fast tier.
        # Two guards keep promotion from fighting demotion: (a) only objects
        # whose whole clock class fits in the pin budget (the hottest class,
        # typically clock=3); (b) never promote more than this compaction
        # demoted, so compactions monotonically free space.  Allocation
        # happens BEFORE the merge set is fixed: a failed allocation keeps
        # the object in the new run (no loss).
        nf = state.fast_keys.shape[0]
        ftgt = jnp.where(demote, fslots, nf)
        fast_keys = state.fast_keys.at[ftgt].set(-1, mode="drop")
        fast_ver = state.fast_ver.at[ftgt].set(0, mode="drop")

        n_dem_total = jnp.sum(demote.astype(jnp.int32))
        sclock, stracked = tracker.lookup_clock(state.tracker, skeys)
        fully_pinned = (probs[jnp.clip(sclock.astype(jnp.int32), 0, 3)]
                        >= 0.999)
        promote_want = (sm & ~superseded & stracked & fully_pinned
                        & (sclock >= cfg.promote_min_clock)) if promote \
            else jnp.zeros_like(sm)
        if cfg.n_tiers > 2:
            # tier-1 tombstone ROWS (deep-boundary delete carriers) are not
            # data: never promote them back to the slab tier
            stomb = state.tombs[0][sslots]
            promote_want = promote_want & ~stomb
        rank = jnp.cumsum(promote_want.astype(jnp.int32)) - 1
        promote_want = promote_want & (rank < n_dem_total)
        pro_slots = alloc_slots(fast_keys, promote_want)
        pro_ok = promote_want & (pro_slots >= 0)
        ptgt = jnp.where(pro_ok, pro_slots, nf)
        fast_keys = fast_keys.at[ptgt].set(skeys, mode="drop")
        fast_vals = state.fast_vals.at[ptgt].set(state.slow_vals[sslots],
                                                 mode="drop")
        fast_ver = fast_ver.at[ptgt].set(1, mode="drop")
        # incremental index maintenance: drop the demoted slots, merge in
        # the promotions
        dropf = jnp.zeros((nf,), bool).at[
            jnp.where(demote, fpos, nf)].set(True, mode="drop")
        fidx_keys, fidx_slots = merge_index_update(
            state.fidx_keys, state.fidx_slots, dropf, skeys, pro_slots,
            pro_ok)

        survive = sm & ~superseded & ~pro_ok

    with jax.named_scope("merge"):
        # ---- merge (sorted; PADKEY sorts to the tail) -----------------------
        if cfg.n_tiers > 2:
            # A tier-0 tombstone cannot simply vanish at boundary 0 when a
            # copy may survive in tiers >= 2: bloom-positive-anywhere-deeper
            # tombstones ride the merge into tier 1 as tombstone ROWS
            # (paper §6 generalized; dropped once no deeper tier remains).
            # Surviving tier-1 tombstone rows are likewise dropped as soon
            # as every deeper bloom goes negative.
            deeper_f = _maybe_deeper(state, cfg, fkeys, below=1)
            deeper_s = _maybe_deeper(state, cfg, skeys, below=1)
            tomb_keep = demote & tomb & deeper_f
            survive = survive & (~stomb | deeper_s)
            f_half = demote_data | tomb_keep
            mtomb_half = jnp.concatenate([tomb_keep, stomb & survive])
        else:
            f_half = demote_data
        mkeys = jnp.concatenate([jnp.where(f_half, fkeys, PADKEY),
                                 jnp.where(survive, skeys, PADKEY)])
        mvals = jnp.concatenate([state.fast_vals[fslots],
                                 state.slow_vals[sslots]])
        order = jnp.argsort(mkeys)
        mkeys, mvals = mkeys[order], mvals[order]
        mvalid = mkeys != PADKEY
        n_merged = jnp.sum(mvalid.astype(jnp.int32))

    with jax.named_scope("write_runs"):
        # ---- free the window runs' slots ------------------------------------
        r = cfg.max_runs
        # map window positions in lo-order back to run ids
        lo_key = jnp.where(state.run_active, state.run_lo, PADKEY)
        order_runs = jnp.argsort(lo_key)
        pos_in_order = jnp.searchsorted(lo_key[order_runs], state.run_lo[
            jnp.clip(run_start, 0, r - 1)])
        win_pos = pos_in_order + jnp.arange(cfg.range_fanout_i,
                                            dtype=jnp.int32)
        win_rids = jnp.where(
            (run_start >= 0) & (jnp.arange(cfg.range_fanout_i) < run_span),
            order_runs[jnp.clip(win_pos, 0, r - 1)], r).astype(jnp.int32)

        # the rows the window read leave the pool: the window runs' rows,
        # and any row of [lo, hi) that a merge wrote when the run directory
        # had no free entry (its run id is max_runs).  Freeing by run id
        # alone would leave such rows behind in the middle of the index
        # range the splice below replaces.
        ns = state.slow_keys.shape[0]
        in_window = jnp.zeros((ns,), bool).at[
            jnp.where(sm, sslots, ns)].set(True, mode="drop")
        slow_keys = jnp.where(in_window, -1, state.slow_keys)
        slow_run = jnp.where(in_window, -1, state.slow_run)

        # ---- write the merged output as sub-runs ----------------------------
        # (the paper writes "new SST file(s)": splitting keeps run sizes
        # bounded)
        sub_of, n_sub = sub_runs(mvalid, n_merged, cfg.run_size)

        new_slots = alloc_slots(slow_keys, mvalid)
        wrote = mvalid & (new_slots >= 0)
        stgt = jnp.where(wrote, new_slots, slow_keys.shape[0])
        slow_keys = slow_keys.at[stgt].set(mkeys, mode="drop")
        slow_vals = state.slow_vals.at[stgt].set(mvals, mode="drop")
        if cfg.n_tiers > 2:
            mtomb = mtomb_half[order]
            tombs0 = jnp.where(in_window, False, state.tombs[0])
            tombs0 = tombs0.at[stgt].set(mtomb, mode="drop")

        run_active = state.run_active.at[win_rids].set(False, mode="drop")
        run_count = state.run_count.at[win_rids].set(0, mode="drop")
        run_lo = state.run_lo
        run_hi = state.run_hi
        free_rids = jnp.nonzero(~run_active, size=n_sub, fill_value=r)[0] \
            .astype(jnp.int32)
        slow_run = slow_run.at[stgt].set(
            free_rids[jnp.clip(sub_of, 0, n_sub - 1)], mode="drop")

    with jax.named_scope("slow_index"):
        # the window read one contiguous stretch of the index (every slow
        # key of [lo, hi)); the merged writes (a sorted prefix of mkeys,
        # all in [lo, hi)) take its place
        sidx_keys, sidx_slots = splice_index_range(
            state.sidx_keys, state.sidx_slots,
            jnp.searchsorted(state.sidx_keys, lo).astype(jnp.int32),
            jnp.sum(sm.astype(jnp.int32)), mkeys, new_slots,
            jnp.sum(wrote.astype(jnp.int32)), cap_slow)

    with jax.named_scope("write_runs"):
        # per-sub-run counts and key bounds
        sub_counts = jnp.zeros((n_sub,), jnp.int32).at[sub_of].add(
            wrote.astype(jnp.int32))
        sub_first = jnp.full((n_sub,), PADKEY, jnp.int32).at[sub_of].min(
            jnp.where(wrote, mkeys, PADKEY))
        # sub-run j owns [first_j (or lo for j=0), first_{j+1}) ; last owns
        # to hi
        sub_lo = jnp.where(jnp.arange(n_sub) == 0, lo, sub_first)
        nxt_first = jnp.concatenate([sub_first[1:],
                                     jnp.array([PADKEY], jnp.int32)])
        sub_hi = jnp.minimum(nxt_first, hi)
        sub_ok = sub_counts > 0
        dir_tgt = jnp.where(sub_ok, free_rids, r)
        run_active = run_active.at[dir_tgt].set(True, mode="drop")
        run_lo = run_lo.at[dir_tgt].set(sub_lo, mode="drop")
        run_hi = run_hi.at[dir_tgt].set(sub_hi, mode="drop")
        run_count = run_count.at[dir_tgt].set(sub_counts, mode="drop")

    with jax.named_scope("blooms"):
        blooms = state.blooms
        for j in range(n_sub):         # static unroll: n_sub is small
            blooms = jax.lax.cond(
                sub_ok[j],
                lambda bl: bloom.set_run(bl, free_rids[j], mkeys,
                                         wrote & (sub_of == j)),
                lambda bl: bl, blooms)

    with jax.named_scope("stats"):
        # ---- tracker location bits ------------------------------------------
        trk = tracker.set_location(state.tracker, fkeys,
                                   jnp.full(fkeys.shape, 1, jnp.int8), demote)
        trk = tracker.set_location(trk, skeys,
                                   jnp.full(skeys.shape, 0, jnp.int8), pro_ok)

        # ---- bucket statistics ----------------------------------------------
        nb = cfg.n_buckets
        fb = bucket_of(cfg, fkeys)
        sb = bucket_of(cfg, skeys)
        mb = bucket_of(cfg, mkeys)
        bucket_fast = state.bucket_fast
        bucket_fast = bucket_fast.at[jnp.where(demote, fb, nb)].add(
            -1, mode="drop")
        bucket_fast = bucket_fast.at[jnp.where(pro_ok, sb, nb)].add(
            1, mode="drop")
        bucket_slow = state.bucket_slow
        bucket_slow = bucket_slow.at[jnp.where(sm, sb, nb)].add(
            -1, mode="drop")
        bucket_slow = bucket_slow.at[jnp.where(wrote, mb, nb)].add(
            1, mode="drop")
        # overlaps within [lo, hi) are fully resolved by the merge
        b_width = max(cfg.key_space // nb, 1)
        edges_lo = jnp.arange(nb, dtype=jnp.int32) * b_width
        cover = jnp.clip((jnp.minimum(edges_lo + b_width, hi)
                          - jnp.maximum(edges_lo, lo)).astype(jnp.float32)
                         / float(b_width), 0.0, 1.0)
        bucket_overlap = (state.bucket_overlap.astype(jnp.float32)
                          * (1.0 - cover)).astype(jnp.int32)

        # ---- counters (object units; bytes derived at report time) ----------
        t_f = jnp.sum(sm.astype(jnp.int32))
        n_dem = jnp.sum(demote_data.astype(jnp.int32))
        n_pro = jnp.sum(pro_ok.astype(jnp.int32))
        n_sup = jnp.sum(superseded.astype(jnp.int32))
        nt = cfg.n_tiers
        rinc = jnp.zeros((nt,), jnp.int32).at[0].set(n_dem).at[1].set(t_f)
        winc = jnp.zeros((nt,), jnp.int32).at[0].set(n_pro).at[1].set(
            n_merged)
        crinc = jnp.zeros((nt,), jnp.int32).at[1].set(t_f)
        ctr = state.ctr._replace(
            compactions=state.ctr.compactions + 1,
            demoted=state.ctr.demoted + n_dem,
            promoted=state.ctr.promoted + n_pro,
            reads=state.ctr.reads + rinc,
            comp_reads=state.ctr.comp_reads + crinc,
            writes=state.ctr.writes + winc,
            comp_by_boundary=state.ctr.comp_by_boundary.at[0].add(1),
            rate_limited=state.ctr.rate_limited
            + jnp.sum((mvalid & ~wrote).astype(jnp.int32)),
        )

        stats = CompactionStats(
            selected_lo=lo, selected_hi=hi, score=scores[best],
            n_demoted=n_dem, n_promoted=n_pro, n_merged=n_merged,
            n_superseded=n_sup, n_run_read=t_f, n_run_written=n_merged)

    new_state = state.update(
        fast_keys=fast_keys, fast_vals=fast_vals, fast_ver=fast_ver,
        fidx_keys=fidx_keys, fidx_slots=fidx_slots,
        slow_keys=slow_keys, slow_vals=slow_vals, slow_run=slow_run,
        sidx_keys=sidx_keys, sidx_slots=sidx_slots,
        run_lo=run_lo, run_hi=run_hi, run_count=run_count,
        run_active=run_active, blooms=blooms, tracker=trk,
        bucket_fast=bucket_fast, bucket_slow=bucket_slow,
        bucket_overlap=bucket_overlap, ctr=ctr)
    if cfg.n_tiers > 2:
        new_state = new_state._replace(
            tombs=(tombs0,) + state.tombs[1:])
    if not with_movement:
        return new_state, stats
    src_tier = jnp.concatenate([jnp.zeros_like(fslots),
                                jnp.ones_like(sslots)])[order]
    src_slot = jnp.concatenate([fslots, sslots])[order]
    mv = Movement(
        m_src_tier=src_tier.astype(jnp.int32),
        m_src_slot=src_slot.astype(jnp.int32),
        m_dst_slot=jnp.where(wrote, new_slots, -1).astype(jnp.int32),
        m_valid=wrote,
        p_src_slot=jnp.where(pro_ok, sslots, -1).astype(jnp.int32),
        p_dst_slot=jnp.where(pro_ok, pro_slots, -1).astype(jnp.int32),
        p_valid=pro_ok,
        m_key=mkeys.astype(jnp.int32),
        boundary=jnp.zeros((), jnp.int32))
    return new_state, stats, mv


def needs_compaction(state: TierState, cfg: TierConfig) -> jax.Array:
    return fast_occupancy(state) >= cfg.high_watermark


def below_low_watermark(state: TierState, cfg: TierConfig) -> jax.Array:
    return fast_occupancy(state) < cfg.low_watermark


# ------------------------------------------- preemptible micro-step drain
#
# With ``EngineConfig.compaction_quantum > 0`` a triggered compaction is
# split into bounded micro-steps: the trigger step commits the LOGICAL
# transition exactly as run-to-completion does (pools, indexes, run
# directory, counters -- so every downstream decision, the rate
# limiter's headroom, the watermark, the §5.3 policy and the final state
# stay bit-identical for ANY quantum), but the PHYSICAL migration -- the
# staged Movement rows and the modeled I/O attribution -- is carried in
# device state (``InFlight``, a field of ``EngineState``) and drained at
# most ``compaction_quantum`` merged rows per engine step.  Each drain
# replays its slice of the staged rows through the tier_compact data
# movers (both backends), guarded so every replayed write is provably
# idempotent: a source row is copied only while the destination still
# holds the same bits, so a put/delete/later-compaction racing the
# in-flight job can never corrupt it.  Reads inside the selected range
# are served by a dual lookup (``inflight_read``) against the
# not-yet-drained source slots until the job commits.


class InFlight(NamedTuple):
    """In-flight compaction carry: the un-drained remainder of triggered
    compaction jobs, plus the latest job's staged Movement rows.

    All arrays are cap-shaped (``cap_fast + cap_slow`` -- per-compaction
    working-set bounds), never pool-shaped: the hot loop stays pool-size
    independent.  ``rem_rows > 0`` <=> a job is in flight.  The ``rem_*``
    category counters may span several overlapping jobs (a later trigger
    stages on top of an un-drained backlog); the staged row arrays always
    describe the LATEST job -- older rows are already bit-resident at
    their destinations (the logical commit wrote them), so dropping their
    replay slice loses no data, only its micro-step attribution."""
    rem_rows: jax.Array         # i32: un-drained merged rows (all jobs)
    rem_run_read: jax.Array     # i32: un-attributed seq run reads
    rem_run_written: jax.Array  # i32: un-attributed seq run writes
    rem_fast_read: jax.Array    # i32: un-attributed demotion reads
    rem_fast_write: jax.Array   # i32: un-attributed promotion writes
    lo: jax.Array               # i32: union of in-flight key ranges
    hi: jax.Array
    score: jax.Array            # f32: latest job's MSC score
    trigger: jax.Array          # i32: latest job's TRIG_* kind
    m_key: jax.Array            # i32[capm] latest job's merged keys, sorted
    m_src_tier: jax.Array       # i32[capm] 0=fast 1=slow
    m_src_slot: jax.Array       # i32[capm]
    m_dst_slot: jax.Array       # i32[capm] destination slow slot (-1 none)
    m_done: jax.Array           # i32: drained merge-row cursor (latest job)
    m_total: jax.Array          # i32: latest job's merged-row count
    boundary: jax.Array = ()    # i32: latest job's boundary (quantized
    #                             jobs are always boundary 0 today; deep
    #                             boundary merges run to completion)


def inflight_cap(cfg: TierConfig) -> int:
    """Static staged-row capacity: one compaction's merge working set."""
    return 2 * cfg.run_size + 2 * cfg.run_size * max(cfg.range_fanout_i, 1)


def init_inflight(cfg: TierConfig) -> InFlight:
    capm = inflight_cap(cfg)
    z = jnp.zeros((), jnp.int32)
    return InFlight(
        rem_rows=z, rem_run_read=z, rem_run_written=z, rem_fast_read=z,
        rem_fast_write=z, lo=z, hi=z, score=jnp.zeros((), jnp.float32),
        trigger=z,
        m_key=jnp.full((capm,), PADKEY, jnp.int32),
        m_src_tier=jnp.zeros((capm,), jnp.int32),
        m_src_slot=jnp.zeros((capm,), jnp.int32),
        m_dst_slot=jnp.full((capm,), -1, jnp.int32),
        m_done=z, m_total=z, boundary=z)


def stage_inflight(fl: InFlight, stats: CompactionStats, mv: Movement,
                   trigger: jax.Array) -> InFlight:
    """Fold one just-committed compaction into the carry (runs inside the
    ``engine.maintenance`` while_loop body, right after ``compact_once``).

    ``rem_rows`` grows by at least 1 even for an empty merge so a job
    with only read/demote work still gets drained (and its commit event
    recorded) on a later step."""
    active = fl.rem_rows > 0
    return fl._replace(
        rem_rows=fl.rem_rows + jnp.maximum(stats.n_merged, 1),
        rem_run_read=fl.rem_run_read + stats.n_run_read,
        rem_run_written=fl.rem_run_written + stats.n_run_written,
        rem_fast_read=fl.rem_fast_read + stats.n_demoted,
        rem_fast_write=fl.rem_fast_write + stats.n_promoted,
        lo=jnp.where(active, jnp.minimum(fl.lo, stats.selected_lo),
                     stats.selected_lo),
        hi=jnp.where(active, jnp.maximum(fl.hi, stats.selected_hi),
                     stats.selected_hi),
        score=stats.score,
        trigger=jnp.asarray(trigger, jnp.int32),
        m_key=mv.m_key, m_src_tier=mv.m_src_tier,
        m_src_slot=mv.m_src_slot, m_dst_slot=mv.m_dst_slot,
        m_done=jnp.zeros((), jnp.int32), m_total=stats.n_merged,
        boundary=jnp.zeros((), jnp.int32))


def _movers(backend: str, interpret: bool | None):
    """Backend-dispatched (select-gather, scatter) row movers (lazy import:
    repro.kernels imports this module's Movement)."""
    if backend == "reference":
        from repro.kernels.tier_compact.ref import (scatter_rows_ref,
                                                    select_gather_rows_ref)
        return select_gather_rows_ref, scatter_rows_ref
    import functools

    from repro.core import backend as backend_mod
    from repro.kernels.tier_compact.tier_compact import (scatter_rows,
                                                         select_gather_rows)
    itp = backend_mod.resolve_interpret(interpret)
    return (functools.partial(select_gather_rows, interpret=itp),
            functools.partial(scatter_rows, interpret=itp))


def drain_quantum(state: TierState, fl: InFlight, quantum: int, *,
                  backend: str = "reference",
                  interpret: bool | None = None
                  ) -> tuple[TierState, InFlight, tuple, jax.Array]:
    """Drain at most ``quantum`` merged rows of the in-flight migration.

    Two halves, both O(quantum) per step (never pool-shaped work):

    * attribution -- take ``k = min(quantum, rem_rows)`` rows off the
      backlog and a proportional share of each modeled-I/O category
      (the final drain takes every remainder exactly, so a job's quanta
      sum to its run-to-completion charge);
    * physical replay -- gather the quantum's slice of the latest job's
      staged source rows through the backend's tier_compact movers and
      scatter them to their destination slow slots.  A row is replayed
      only while destination key and bits still match its source
      (idempotence guard): interleaved client writes or a later
      compaction may have recycled either slot, in which case the row is
      already bit-final and the copy is skipped.

    Returns ``(state', fl', (run_read, run_written, fast_read,
    fast_write), k)`` -- the drained category counts price the step's
    quantum (``repro.obs.cost.drain_io_us``).
    """
    k = jnp.minimum(jnp.int32(quantum), fl.rem_rows)
    rem_after = fl.rem_rows - k
    finish = (fl.rem_rows > 0) & (rem_after == 0)
    denom = jnp.maximum(fl.rem_rows.astype(jnp.float32), 1.0)

    def take(rem: jax.Array) -> jax.Array:
        prop = jnp.floor(rem.astype(jnp.float32)
                         * k.astype(jnp.float32) / denom).astype(jnp.int32)
        return jnp.where(finish, rem, jnp.minimum(prop, rem))

    d_rr, d_rw = take(fl.rem_run_read), take(fl.rem_run_written)
    d_fr, d_fw = take(fl.rem_fast_read), take(fl.rem_fast_write)

    # ---- physical replay of the staged window [m_done, m_done + k) ------
    capm = fl.m_key.shape[0]
    q = min(max(int(quantum), 1), capm)
    start = jnp.clip(fl.m_done, 0, capm - q)
    sl = lambda a: lax.dynamic_slice(a, (start,), (q,))
    keys, tier_src = sl(fl.m_key), sl(fl.m_src_tier)
    src, dst = sl(fl.m_src_slot), sl(fl.m_dst_slot)
    pos = start + jnp.arange(q, dtype=jnp.int32)
    in_q = (pos >= fl.m_done) & (pos < fl.m_done + k) & (pos < fl.m_total)
    nf, ns = state.fast_keys.shape[0], state.slow_keys.shape[0]
    src_slow = tier_src != 0
    idx = jnp.where(src_slow, jnp.clip(src, 0, ns - 1),
                    jnp.clip(src, 0, nf - 1))
    sel, sc = _movers(backend, interpret)
    rows = sel(state.fast_vals, state.slow_vals, src_slow, idx)
    dst_c = jnp.clip(dst, 0, ns - 1)
    live = (in_q & (keys != PADKEY) & (dst >= 0)
            & (state.slow_keys[dst_c] == keys)
            & jnp.all(rows == state.slow_vals[dst_c], axis=1))
    slow_vals = sc(state.slow_vals, jnp.where(live, dst, ns), rows, live)

    fl = fl._replace(
        rem_rows=rem_after,
        rem_run_read=fl.rem_run_read - d_rr,
        rem_run_written=fl.rem_run_written - d_rw,
        rem_fast_read=fl.rem_fast_read - d_fr,
        rem_fast_write=fl.rem_fast_write - d_fw,
        m_done=jnp.minimum(fl.m_done + k, fl.m_total))
    return (state.update(slow_vals=slow_vals), fl,
            (d_rr, d_rw, d_fr, d_fw), k)


def inflight_read(state: TierState, fl: InFlight, keys: jax.Array,
                  vals: jax.Array, found: jax.Array, src: jax.Array
                  ) -> jax.Array:
    """Dual lookup against a half-migrated range: a get whose key sits in
    the in-flight range and whose staged merge row has NOT been drained
    yet is served from the un-migrated SOURCE slot (the old run / the
    demoted fast slot) instead of the destination -- the paper's reads
    racing an in-progress compaction.  Consistency guard as in
    ``drain_quantum``: the source is used only while its bits still match
    the committed destination, so the returned value is bit-identical to
    the logical lookup for any quantum (pinned by the equivalence
    property test)."""
    active = fl.rem_rows > 0
    in_range = (keys >= fl.lo) & (keys < fl.hi)
    pos = jnp.clip(jnp.searchsorted(fl.m_key, keys), 0,
                   fl.m_key.shape[0] - 1)
    staged = (fl.m_key[pos] == keys) & (pos >= fl.m_done) \
        & (pos < fl.m_total)
    nf, ns = state.fast_keys.shape[0], state.slow_keys.shape[0]
    s_tier, s_slot, s_dst = (fl.m_src_tier[pos], fl.m_src_slot[pos],
                             fl.m_dst_slot[pos])
    src_slow = s_tier != 0
    sval = jnp.where(src_slow[:, None],
                     state.slow_vals[jnp.clip(s_slot, 0, ns - 1)],
                     state.fast_vals[jnp.clip(s_slot, 0, nf - 1)])
    dst_c = jnp.clip(s_dst, 0, ns - 1)
    coherent = (s_dst >= 0) & (state.slow_keys[dst_c] == keys) \
        & jnp.all(sval == state.slow_vals[dst_c], axis=1)
    use = active & in_range & staged & coherent & found & (src == 1)
    return jnp.where(use[:, None], sval, vals)


def defer_adjust(delta: Counters, before: InFlight,
                 after: InFlight) -> Counters:
    """Re-attribute one step's counter delta for the obs plane: subtract
    the net I/O DEFERRED into the carry this step (staged minus drained,
    per category).  The trigger step is charged only its first quantum;
    later steps are charged the quanta they drain -- counters themselves
    stay committed at trigger time (total modeled I/O is unchanged)."""
    n_rr = after.rem_run_read - before.rem_run_read
    n_rw = after.rem_run_written - before.rem_run_written
    n_fr = after.rem_fast_read - before.rem_fast_read
    n_fw = after.rem_fast_write - before.rem_fast_write
    # quantized jobs are boundary-0: defer tier-0 random and tier-1
    # sequential categories (values identical to the pair-era scalars)
    return delta._replace(
        reads=delta.reads.at[0].add(-n_fr).at[1].add(-n_rr),
        comp_reads=delta.comp_reads.at[1].add(-n_rr),
        writes=delta.writes.at[0].add(-n_fw).at[1].add(-n_rw))


# ----------------------------------------------- deep (run-to-run) merges
#
# Boundaries >= 1 connect two run-structured tiers: there is no slab, no
# clock tracker, no pin/promote decision (paper §5.3 promotion always
# targets tier i-1 of the SLAB boundary -- hot objects climb one level
# per compaction, and only boundary 0 has the popularity signal), so a
# deep compaction is a plain LSM-style merge: pick the upper-tier run
# whose migration buys the most bytes per unit of boundary-priced I/O,
# merge it with every overlapping lower-tier run, and append the result
# as fresh lower-tier sub-runs.


def _maybe_deeper(state: TierState, cfg: TierConfig, keys: jax.Array,
                  below: int) -> jax.Array:
    """OR of per-tier bloom answers over every tier STRICTLY below
    ``below`` -- "may a copy of this key survive deeper than tier
    ``below``?".  Drives tombstone retention during merges."""
    m = jnp.zeros(keys.shape, bool)
    for t in range(below + 1, cfg.n_tiers):
        rid = run_of_keys(state, keys, tier=t)
        m = m | bloom.query_per_key(state.dir_blooms[t - 1], rid, keys)
    return m


def compact_boundary(state: TierState, cfg: TierConfig, boundary: int, *,
                     cost=None,
                     cap_up: int | None = None,
                     cap_lo: int | None = None,
                     with_movement: bool = False):
    """One deep compaction at static ``boundary`` (>= 1): migrate the
    best-scoring tier-``boundary`` run down into tier ``boundary + 1``.

    Selection scores every active upper run with THIS boundary's cost
    coefficients (``msc.select_boundary_run``); the merge then

      1. reads the selected run's rows (sequential upper-tier I/O) and
         every overlapping lower run's rows (sequential lower-tier I/O);
      2. drops lower copies superseded by the migrating run, drops
         tombstone rows whose key is bloom-negative in every deeper
         tier, carries the rest of the tombstones down;
      3. merge-sorts the survivors into fresh lower-tier sub-runs
         (``sub_runs``; new Blooms, directory entries, incremental
         index maintenance on BOTH tiers).

    Counters: both windows land in per-tier ``reads``/``comp_reads``,
    the output in ``writes[boundary+1]``, and the job increments
    ``comp_by_boundary[boundary]``.  Returns ``(state', stats[, mv])``
    with ``stats.n_run_read`` covering BOTH windows (the obs plane
    prices the whole event with ``compaction_io_us(boundary=...)``;
    ``cost.boundary_io_us`` is the exact split when the caller keeps the
    windows separate)."""
    assert boundary >= 1, "boundary 0 is compact_once's slab merge"
    u, l = boundary, boundary + 1
    du, dl = u - 1, l - 1
    # upper window = ONE run, and runs are written as sub-runs of fewer
    # than 2*run_size rows everywhere (``sub_runs``), so 2x is an upper
    # bound.  The lower window is every overlapped run: a wide upper run
    # can overlap ALL of them, and truncating the window while freeing
    # the sources wholesale would lose rows -- cap it at the static bound
    # instead.
    cap_up = cap_up or 2 * cfg.run_size
    cap_lo = cap_lo or min(cfg.tier_sizes[l],
                           2 * cfg.max_runs * cfg.run_size)
    r = cfg.max_runs
    nl = state.keys[l].shape[0]

    rid, lo, hi, score, ov = msc.select_boundary_run(
        state, cfg, boundary, cost=cost)
    # output hull: the selected range plus every overlapped lower run's
    # range (lower runs are mutually disjoint and each intersects
    # [lo, hi), so the hull contains no foreign lower run)
    out_lo = jnp.minimum(lo, jnp.min(jnp.where(ov, state.dir_lo[dl],
                                               PADKEY)))
    out_hi = jnp.maximum(hi, jnp.max(jnp.where(ov, state.dir_hi[dl],
                                               -1)))

    # ---- upper window: the selected run's rows --------------------------
    upos, um = segment_in_range(state.idx_keys[u], lo, hi, cap_up)
    ukeys = jnp.where(um, state.idx_keys[u][upos], PADKEY)
    uslots = jnp.where(um, state.idx_slots[u][upos], 0)
    utomb = (state.tombs[du][uslots] if state.tombs
             else jnp.zeros_like(um)) & um

    # ---- lower window: all rows of the overlapped runs ------------------
    lpos, lm = segment_in_range(state.idx_keys[l], out_lo, out_hi, cap_lo)
    lkeys = jnp.where(lm, state.idx_keys[l][lpos], PADKEY)
    lslots = jnp.where(lm, state.idx_slots[l][lpos], 0)
    ltomb = (state.tombs[dl][lslots] if state.tombs
             else jnp.zeros_like(lm)) & lm
    _, in_up = sorted_lookup(state.idx_keys[u], state.idx_slots[u], lkeys)
    superseded = in_up & lm & (lkeys >= lo) & (lkeys < hi)

    # ---- tombstone retention --------------------------------------------
    if l == cfg.n_tiers - 1:
        keep_ut = jnp.zeros_like(um)
        keep_lt = jnp.zeros_like(lm)
    else:
        keep_ut = _maybe_deeper(state, cfg, ukeys, below=l)
        keep_lt = _maybe_deeper(state, cfg, lkeys, below=l)
    ukeep = um & (~utomb | keep_ut)
    lkeep = lm & ~superseded & (~ltomb | keep_lt)

    # ---- merge-sort ------------------------------------------------------
    mkeys = jnp.concatenate([jnp.where(ukeep, ukeys, PADKEY),
                             jnp.where(lkeep, lkeys, PADKEY)])
    mvals = jnp.concatenate([state.vals[u][uslots],
                             state.vals[l][lslots]])
    mtomb = jnp.concatenate([utomb & ukeep, ltomb & lkeep])
    order = jnp.argsort(mkeys)
    mkeys, mvals, mtomb = mkeys[order], mvals[order], mtomb[order]
    mvalid = mkeys != PADKEY
    n_merged = jnp.sum(mvalid.astype(jnp.int32))

    # ---- free the sources -----------------------------------------------
    in_up_win = state.runs[du] == rid
    up_keys = jnp.where(in_up_win, -1, state.keys[u])
    up_runs = jnp.where(in_up_win, -1, state.runs[du])
    uidx_keys, uidx_slots = merge_index_update(
        state.idx_keys[u], state.idx_slots[u],
        in_up_win[state.idx_slots[u]] & (state.idx_keys[u] != PADKEY),
        jnp.full((1,), PADKEY, jnp.int32), jnp.full((1,), -1, jnp.int32),
        jnp.zeros((1,), bool))
    udir_act = state.dir_active[du].at[rid].set(False)
    udir_cnt = state.dir_count[du].at[rid].set(0)

    lrun = state.runs[dl]
    in_lo_win = (lrun >= 0) & ov[jnp.clip(lrun, 0, r - 1)]
    lo_keys = jnp.where(in_lo_win, -1, state.keys[l])
    lo_runs = jnp.where(in_lo_win, -1, lrun)

    # ---- write merged output into the lower tier ------------------------
    sub_of, n_sub = sub_runs(mvalid, n_merged, cfg.run_size)
    new_slots = alloc_slots(lo_keys, mvalid)
    wrote = mvalid & (new_slots >= 0)
    stgt = jnp.where(wrote, new_slots, nl)
    lo_keys = lo_keys.at[stgt].set(mkeys, mode="drop")
    lo_vals = state.vals[l].at[stgt].set(mvals, mode="drop")

    ldir_act = state.dir_active[dl].at[
        jnp.where(ov, jnp.arange(r), r)].set(False, mode="drop")
    ldir_cnt = state.dir_count[dl].at[
        jnp.where(ov, jnp.arange(r), r)].set(0, mode="drop")
    ldir_lo, ldir_hi = state.dir_lo[dl], state.dir_hi[dl]
    free_rids = jnp.nonzero(~ldir_act, size=n_sub, fill_value=r)[0] \
        .astype(jnp.int32)
    lo_runs = lo_runs.at[stgt].set(
        free_rids[jnp.clip(sub_of, 0, n_sub - 1)], mode="drop")
    lidx_keys, lidx_slots = merge_index_update(
        state.idx_keys[l], state.idx_slots[l],
        in_lo_win[state.idx_slots[l]] & (state.idx_keys[l] != PADKEY),
        mkeys, new_slots, wrote)

    sub_counts = jnp.zeros((n_sub,), jnp.int32).at[sub_of].add(
        wrote.astype(jnp.int32))
    sub_first = jnp.full((n_sub,), PADKEY, jnp.int32).at[sub_of].min(
        jnp.where(wrote, mkeys, PADKEY))
    sub_lo = jnp.where(jnp.arange(n_sub) == 0, out_lo, sub_first)
    nxt_first = jnp.concatenate([sub_first[1:],
                                 jnp.array([PADKEY], jnp.int32)])
    sub_hi = jnp.minimum(nxt_first, out_hi)
    sub_ok = sub_counts > 0
    dir_tgt = jnp.where(sub_ok, free_rids, r)
    ldir_act = ldir_act.at[dir_tgt].set(True, mode="drop")
    ldir_lo = ldir_lo.at[dir_tgt].set(sub_lo, mode="drop")
    ldir_hi = ldir_hi.at[dir_tgt].set(sub_hi, mode="drop")
    ldir_cnt = ldir_cnt.at[dir_tgt].set(sub_counts, mode="drop")
    # fori_loop, not a static unroll: n_sub scales with the (pool-sized)
    # lower window cap, and valid rows form a contiguous sorted prefix,
    # so sub-run j's rows start at position j*run_size and number fewer
    # than 2*run_size -- a dynamic_slice keeps each bloom build run-sized
    # (the mask drops the next sub-run's rows and the clamped tail's).
    span = min(2 * cfg.run_size, mkeys.shape[0])

    def _bloom_body(j, bl):
        ks = lax.dynamic_slice(mkeys, (j * cfg.run_size,), (span,))
        vm = lax.dynamic_slice(wrote & (sub_of == j), (j * cfg.run_size,),
                               (span,))
        return lax.cond(
            sub_ok[j],
            lambda b: bloom.set_run(b, free_rids[j], ks, vm),
            lambda b: b, bl)

    lblooms = lax.fori_loop(0, n_sub, _bloom_body, state.dir_blooms[dl])

    # ---- tombstone marks ------------------------------------------------
    if state.tombs:
        utombs = jnp.where(in_up_win, False, state.tombs[du])
        ltombs = jnp.where(in_lo_win, False, state.tombs[dl])
        ltombs = ltombs.at[stgt].set(mtomb, mode="drop")
        tombs = (state.tombs[:du] + (utombs,) + (ltombs,)
                 + state.tombs[dl + 1:])
    else:
        tombs = state.tombs

    # ---- counters -------------------------------------------------------
    nt = cfg.n_tiers
    t_u = jnp.sum(um.astype(jnp.int32))
    t_l = jnp.sum(lm.astype(jnp.int32))
    rinc = jnp.zeros((nt,), jnp.int32).at[u].set(t_u).at[l].set(t_l)
    winc = jnp.zeros((nt,), jnp.int32).at[l].set(n_merged)
    ctr = state.ctr._replace(
        compactions=state.ctr.compactions + 1,
        reads=state.ctr.reads + rinc,
        comp_reads=state.ctr.comp_reads + rinc,
        writes=state.ctr.writes + winc,
        comp_by_boundary=state.ctr.comp_by_boundary.at[boundary].add(1),
        rate_limited=state.ctr.rate_limited
        + jnp.sum((mvalid & ~wrote).astype(jnp.int32)),
    )

    def tset(t, i, v):
        return t[:i] + (v,) + t[i + 1:]

    new_state = state._replace(
        keys=tset(tset(state.keys, u, up_keys), l, lo_keys),
        vals=tset(state.vals, l, lo_vals),
        runs=tset(tset(state.runs, du, up_runs), dl, lo_runs),
        tombs=tombs,
        idx_keys=tset(tset(state.idx_keys, u, uidx_keys), l, lidx_keys),
        idx_slots=tset(tset(state.idx_slots, u, uidx_slots),
                       l, lidx_slots),
        dir_lo=tset(state.dir_lo, dl, ldir_lo),
        dir_hi=tset(state.dir_hi, dl, ldir_hi),
        dir_count=tset(tset(state.dir_count, du, udir_cnt),
                       dl, ldir_cnt),
        dir_active=tset(tset(state.dir_active, du, udir_act),
                        dl, ldir_act),
        dir_blooms=tset(state.dir_blooms, dl, lblooms),
        ctr=ctr)
    zero = jnp.zeros((), jnp.int32)
    stats = CompactionStats(
        selected_lo=out_lo, selected_hi=out_hi, score=score,
        n_demoted=zero, n_promoted=zero, n_merged=n_merged,
        n_superseded=jnp.sum(superseded.astype(jnp.int32)),
        n_run_read=t_u + t_l, n_run_written=n_merged)
    if not with_movement:
        return new_state, stats
    src_tier = jnp.concatenate([jnp.full_like(uslots, u),
                                jnp.full_like(lslots, l)])[order]
    src_slot = jnp.concatenate([uslots, lslots])[order]
    mv = Movement(
        m_src_tier=src_tier.astype(jnp.int32),
        m_src_slot=src_slot.astype(jnp.int32),
        m_dst_slot=jnp.where(wrote, new_slots, -1).astype(jnp.int32),
        m_valid=wrote,
        p_src_slot=jnp.full((cap_lo,), -1, jnp.int32),
        p_dst_slot=jnp.full((cap_lo,), -1, jnp.int32),
        p_valid=jnp.zeros((cap_lo,), bool),
        m_key=mkeys.astype(jnp.int32),
        boundary=jnp.full((), boundary, jnp.int32))
    return new_state, stats, mv


def tier_over_watermark(state: TierState, cfg: TierConfig,
                        tier: int) -> jax.Array:
    """Occupancy trigger of the tier ``tier`` -> ``tier + 1`` boundary
    (the same §4.2 watermarks apply at every boundary)."""
    from repro.core.tiers import tier_occupancy
    return tier_occupancy(state, tier) >= cfg.high_watermark


def tier_below_low(state: TierState, cfg: TierConfig,
                   tier: int) -> jax.Array:
    from repro.core.tiers import tier_occupancy
    return tier_occupancy(state, tier) < cfg.low_watermark
