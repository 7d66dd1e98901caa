"""TieredStore: PrismDB's tiered data layout as a tier LIST, functional
in JAX.

Tier 0 (paper: NVM slabs / here: HBM slab pool)
  * fixed-slot unsorted pool -> random in-place writes are O(1)
  * a sorted (key -> slot) index plays the paper's DRAM B-tree role

Tiers 1..T-1 (paper: QLC SSTs in a log / here: host-memory runs)
  * slotted pools whose slots carry a run id; runs are immutable,
    key-sorted, written append-only by compaction (LFS-style: new runs
    appended, old runs freed) -> all lower-tier writes are large and
    sequential
  * one run directory (lo/hi/count) per tier is the paper's manifest
  * one Bloom filter per run, held on the fast tier

The classic PrismDB pair is the T=2 instance: ``fast_* == tier 0``,
``slow_* == tier 1``.  Those legacy names survive as read properties
(and as ``update()`` keyword aliases) so the pair-era call sites keep
working, and the T=2 compiled graph is bit-identical to the historical
two-field layout -- same leaves, same shapes, same op order.

All shapes static; per-tier slot counts may differ, so pools ride as
ragged-by-static-shape tuples of per-tier leaves (not one stacked
array).  I/O accounting (the quantity MSC's cost term optimizes) is
threaded through every op.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bloom, tracker
from repro.core.tracker import TrackerState
from repro.core.utils import (PADKEY, alloc_slots, build_sorted_index,
                              dedupe_keep_last, merge_index_update,
                              sorted_lookup, sorted_position)


class TierConfig(NamedTuple):
    key_space: int = 1 << 20        # keys live in [0, key_space)
    fast_slots: int = 1 << 14       # tier-0 capacity (objects)
    slow_slots: int = 1 << 17       # last-tier capacity (objects)
    value_width: int = 4            # payload lanes (float32) per object
    value_bytes: int = 1024         # *modeled* object size (paper: ~1 KB)
    max_runs: int = 256
    run_size: int = 4096            # target objects per run (SST size)
    bloom_bits_per_run: int = 1 << 15
    tracker_slots: int = 1 << 16    # paper: ~10-20% of key space
    n_buckets: int = 256            # approx-MSC buckets
    pin_threshold: float = 0.7      # paper default (§7)
    promote_min_clock: int = 3      # promote only the hottest clock class
    high_watermark: float = 0.98    # paper §4.2 (every tier boundary)
    low_watermark: float = 0.95
    range_fanout_i: int = 1         # compaction key range = i consecutive runs
    power_k: int = 8                # power-of-k range candidates (§A.1)
    tier_slots: tuple = ()          # N-tier slot counts; () = legacy pair

    @property
    def tier_sizes(self) -> tuple:
        """Per-tier slot counts, hottest first.  Empty ``tier_slots``
        resolves to the legacy ``(fast_slots, slow_slots)`` pair."""
        return tuple(self.tier_slots) or (self.fast_slots, self.slow_slots)

    @property
    def n_tiers(self) -> int:
        return len(self.tier_sizes)


# update() keyword aliases: legacy scalar counter names address the top
# two tiers of the corresponding per-tier vector (exact at T=2; at T>2
# "slow" means tier 1 -- the facades that still write these are 2-tier).
_LEGACY_CTR = {
    "hits_fast": ("hits", 0), "hits_slow": ("hits", 1),
    "fast_reads": ("reads", 0), "slow_reads": ("reads", 1),
    "fast_writes": ("writes", 0), "slow_writes": ("writes", 1),
}


class Counters(NamedTuple):
    """Operation counters in OBJECT units (fixed-size objects; bytes are
    derived as count * cfg.value_bytes at report time -- keeps everything
    int32-safe without x64).

    ``hits/reads/writes/comp_reads/scan_reads`` are i32[T] per-tier
    vectors (entry t = tier t); ``comp_by_boundary`` is i32[T-1] (entry
    b = compactions committed at the tier b -> b+1 boundary).  The
    pair-era scalar names are derived properties for one release."""
    gets: jax.Array
    puts: jax.Array
    hits: jax.Array            # i32[T] per-tier read hits
    misses: jax.Array
    reads: jax.Array           # i32[T] objects read per tier (any cause)
    writes: jax.Array          # i32[T] objects written per tier
    bloom_probes: jax.Array
    bloom_fps: jax.Array
    consolidations: jax.Array  # periodic full index rebuilds (fallback)
    comp_reads: jax.Array      # i32[T] sequential reads issued by
    #                            compactions, per tier (entry 0 unused:
    #                            tier-0 compaction reads are random)
    scans: jax.Array           # range-scan lanes served
    scan_objs: jax.Array       # objects returned by scans (any tier)
    scan_reads: jax.Array      # i32[T] sequential reads issued by scans,
    #                            per tier (entry 0 unused: tier-0 scan
    #                            reads are random slab reads)
    compactions: jax.Array
    comp_by_boundary: jax.Array  # i32[T-1] compactions per boundary
    demoted: jax.Array
    promoted: jax.Array
    rate_limited: jax.Array

    @staticmethod
    def zeros(n_tiers: int = 2) -> "Counters":
        z = jnp.zeros((), dtype=jnp.int32)
        v = jnp.zeros((n_tiers,), dtype=jnp.int32)
        b = jnp.zeros((n_tiers - 1,), dtype=jnp.int32)
        return Counters(
            gets=z, puts=z, hits=v, misses=z, reads=v, writes=v,
            bloom_probes=z, bloom_fps=z, consolidations=z, comp_reads=v,
            scans=z, scan_objs=z, scan_reads=v, compactions=z,
            comp_by_boundary=b, demoted=z, promoted=z, rate_limited=z)

    # ---- pair-era derived scalars (kept for one release) ----------------
    @property
    def hits_fast(self) -> jax.Array:
        return self.hits[..., 0]

    @property
    def hits_slow(self) -> jax.Array:
        return jnp.sum(self.hits[..., 1:], axis=-1)

    @property
    def fast_reads(self) -> jax.Array:
        return self.reads[..., 0]

    @property
    def slow_reads(self) -> jax.Array:
        return jnp.sum(self.reads[..., 1:], axis=-1)

    @property
    def fast_writes(self) -> jax.Array:
        return self.writes[..., 0]

    @property
    def slow_writes(self) -> jax.Array:
        return jnp.sum(self.writes[..., 1:], axis=-1)

    def update(self, **kw) -> "Counters":
        """``_replace`` that also accepts the pair-era scalar names,
        mapping each onto its slot in the per-tier vector."""
        direct = {}
        for k, v in kw.items():
            m = _LEGACY_CTR.get(k)
            if m is None:
                direct[k] = v
            else:
                f, i = m
                cur = direct.get(f, getattr(self, f))
                direct[f] = cur.at[..., i].set(
                    jnp.asarray(v, cur.dtype))
        return self._replace(**direct)


# update() aliases: legacy pair-era field name -> (tuple field, index).
_LEGACY_STATE = {
    "fast_keys": ("keys", 0), "slow_keys": ("keys", 1),
    "fast_vals": ("vals", 0), "slow_vals": ("vals", 1),
    "fidx_keys": ("idx_keys", 0), "sidx_keys": ("idx_keys", 1),
    "fidx_slots": ("idx_slots", 0), "sidx_slots": ("idx_slots", 1),
    "slow_run": ("runs", 0),
    "run_lo": ("dir_lo", 0), "run_hi": ("dir_hi", 0),
    "run_count": ("dir_count", 0), "run_active": ("dir_active", 0),
    "blooms": ("dir_blooms", 0),
}


class TierState(NamedTuple):
    """The tier list.  Tuple fields hold one leaf per tier (``keys``,
    ``vals``, ``idx_keys``, ``idx_slots``: T entries, hottest first) or
    one leaf per run-structured tier (``runs``, ``tombs``, ``dir_*``:
    T-1 entries, entry t-1 describing tier t)."""
    keys: tuple               # i32[N_t] per tier, -1 free
    vals: tuple               # f32[N_t, V] per tier
    fast_ver: jax.Array       # i32[N_0]; < 0 marks a tier-0 tombstone
    runs: tuple               # i32[N_t] run id per slot (-1 free), t >= 1
    tombs: tuple              # bool[N_t] tombstone rows, t >= 1; the
    #                           EMPTY tuple at T=2 (a pair has no
    #                           mid-tier to carry deletes through)
    idx_keys: tuple           # i32[N_t] sorted (PADKEY pad), per tier
    idx_slots: tuple          # i32[N_t], per tier
    dir_lo: tuple             # i32[R] per run-structured tier
    dir_hi: tuple             # i32[R]
    dir_count: tuple          # i32[R]
    dir_active: tuple         # bool[R]
    dir_blooms: tuple         # u32[R, W]
    # popularity
    tracker: TrackerState
    # approx-MSC bucket statistics for boundary 0 (incrementally kept)
    bucket_fast: jax.Array    # i32[B] live tier-0 keys per bucket
    bucket_slow: jax.Array    # i32[B] live tier-1 keys per bucket
    bucket_overlap: jax.Array # i32[B] est. tier-0∩tier-1 keys per bucket
    ctr: Counters

    @property
    def n_tiers(self) -> int:
        return len(self.keys)

    # ---- pair-era read aliases ------------------------------------------
    @property
    def fast_keys(self) -> jax.Array:
        return self.keys[0]

    @property
    def fast_vals(self) -> jax.Array:
        return self.vals[0]

    @property
    def fidx_keys(self) -> jax.Array:
        return self.idx_keys[0]

    @property
    def fidx_slots(self) -> jax.Array:
        return self.idx_slots[0]

    @property
    def slow_keys(self) -> jax.Array:
        return self.keys[1]

    @property
    def slow_vals(self) -> jax.Array:
        return self.vals[1]

    @property
    def slow_run(self) -> jax.Array:
        return self.runs[0]

    @property
    def sidx_keys(self) -> jax.Array:
        return self.idx_keys[1]

    @property
    def sidx_slots(self) -> jax.Array:
        return self.idx_slots[1]

    @property
    def run_lo(self) -> jax.Array:
        return self.dir_lo[0]

    @property
    def run_hi(self) -> jax.Array:
        return self.dir_hi[0]

    @property
    def run_count(self) -> jax.Array:
        return self.dir_count[0]

    @property
    def run_active(self) -> jax.Array:
        return self.dir_active[0]

    @property
    def blooms(self) -> jax.Array:
        return self.dir_blooms[0]

    def update(self, **kw) -> "TierState":
        """``_replace`` that also accepts the pair-era field names,
        rewriting the addressed entry of the owning per-tier tuple."""
        direct = {}
        for k, v in kw.items():
            m = _LEGACY_STATE.get(k)
            if m is None:
                direct[k] = v
            else:
                f, i = m
                cur = direct.get(f, getattr(self, f))
                direct[f] = cur[:i] + (v,) + cur[i + 1:]
        return self._replace(**direct)


def init(cfg: TierConfig, dtype=jnp.float32) -> TierState:
    sizes = cfg.tier_sizes
    r, v = cfg.max_runs, cfg.value_width
    idx = [build_sorted_index(jnp.full((n,), -1, jnp.int32))
           for n in sizes]
    return TierState(
        keys=tuple(jnp.full((n,), -1, jnp.int32) for n in sizes),
        vals=tuple(jnp.zeros((n, v), dtype) for n in sizes),
        fast_ver=jnp.zeros((sizes[0],), jnp.int32),
        runs=tuple(jnp.full((n,), -1, jnp.int32) for n in sizes[1:]),
        tombs=(() if len(sizes) == 2 else
               tuple(jnp.zeros((n,), bool) for n in sizes[1:])),
        idx_keys=tuple(k for k, _ in idx),
        idx_slots=tuple(s for _, s in idx),
        dir_lo=tuple(jnp.full((r,), PADKEY, jnp.int32) for _ in sizes[1:]),
        dir_hi=tuple(jnp.full((r,), PADKEY, jnp.int32) for _ in sizes[1:]),
        dir_count=tuple(jnp.zeros((r,), jnp.int32) for _ in sizes[1:]),
        dir_active=tuple(jnp.zeros((r,), bool) for _ in sizes[1:]),
        dir_blooms=tuple(bloom.init(r, cfg.bloom_bits_per_run)
                         for _ in sizes[1:]),
        tracker=tracker.init(cfg.tracker_slots),
        bucket_fast=jnp.zeros((cfg.n_buckets,), jnp.int32),
        bucket_slow=jnp.zeros((cfg.n_buckets,), jnp.int32),
        bucket_overlap=jnp.zeros((cfg.n_buckets,), jnp.int32),
        ctr=Counters.zeros(len(sizes)),
    )


def bucket_of(cfg: TierConfig, keys: jax.Array) -> jax.Array:
    width = max(cfg.key_space // cfg.n_buckets, 1)
    return jnp.clip(keys // width, 0, cfg.n_buckets - 1).astype(jnp.int32)


def tier_occupancy(state: TierState, t: int) -> jax.Array:
    used = jnp.sum((state.keys[t] >= 0).astype(jnp.int32))
    return used.astype(jnp.float32) / state.keys[t].shape[0]


def fast_occupancy(state: TierState) -> jax.Array:
    return tier_occupancy(state, 0)


def free_fast_slots(state: TierState) -> jax.Array:
    return jnp.sum((state.keys[0] < 0).astype(jnp.int32))


def run_of_keys(state: TierState, keys: jax.Array,
                tier: int = 1) -> jax.Array:
    """int32[n] covering-run id per key (-1 = none) in run-structured
    ``tier``.  Runs hold disjoint key ranges so at most one run covers a
    key."""
    lo, hi = state.dir_lo[tier - 1], state.dir_hi[tier - 1]
    act = state.dir_active[tier - 1]
    cover = (act[:, None]
             & (lo[:, None] <= keys[None, :])
             & (keys[None, :] < hi[:, None]))
    any_cover = jnp.any(cover, axis=0)
    rid = jnp.argmax(cover, axis=0).astype(jnp.int32)
    return jnp.where(any_cover, rid, -1)


# ------------------------------------------------- point ops (one pass)

def apply_point_ops(state: TierState, cfg: TierConfig, keys: jax.Array,
                    vals: jax.Array, valid: jax.Array, *,
                    is_put, is_get, is_del,
                    backend: str = "reference",
                    interpret: bool | None = None
                    ) -> tuple[TierState, jax.Array, jax.Array, jax.Array]:
    """Branchless put/get/delete: one masked structure-of-arrays pass.

    The kind flags may be traced booleans (at most one true), so a stacked
    op stream runs every batch through ONE compiled body -- no ``lax.switch``
    materializing a pool-sized pass-through copy per branch (the XLA CPU
    regression the HLO copy-budget test guards).  All three lanes share the
    index lookups and the bloom probes; pool writes are scatters whose
    targets are masked out-of-bounds (``mode="drop"``) on inactive lanes,
    and the sorted tier-0 index is maintained with a single
    ``merge_index_update``.

    Returns ``(state', vals, found, source)``; the get-lane outputs are
    garbage unless ``is_get``.  ``source`` is the tier index that served
    the hit (-1 = miss).

    put    (paper §4.2): existing tier-0 objects update in place, fresh
           keys take a free slot.
    get    (paper §4.1): tier-0 index -> then tier by tier downward,
           bloom -> run lookup; every bloom-positive probe of a lower
           tier is charged a read on that tier, false positives
           included.  A mid-tier tombstone row is a definitive miss
           (it shadows deeper copies), exactly as a tier-0 tombstone
           hides the whole lower hierarchy.
    delete (paper §6): tier-0 copies freed; keys that may survive on ANY
           lower tier leave a tombstone in tier 0 (cleared at
           compaction).

    The lower-tier walk unrolls statically over ``n_tiers``; at T=2 the
    single iteration traces exactly the historical pair graph.

    ``backend`` statically routes the tracker update (the per-access
    §4.3 hot-path primitive) through the Pallas clock_update kernel;
    the default traces exactly the reference path.
    """
    n_tiers = len(state.keys)
    nf = state.keys[0].shape[0]
    nb = cfg.n_buckets
    keep = dedupe_keep_last(keys, valid)

    # ---- shared lookups -------------------------------------------------
    fpos, flook = sorted_position(state.idx_keys[0], keys)
    fslot = state.idx_slots[0][fpos]
    tomb = state.fast_ver[jnp.clip(fslot, 0)] < 0
    # raw per-lower-tier bloom answers ("key may live in tier t"); the
    # delete lane needs the OR across every lower tier
    maybe_raw = []
    for t in range(1, n_tiers):
        rid = run_of_keys(state, keys, tier=t)
        maybe_raw.append(bloom.query_per_key(state.dir_blooms[t - 1],
                                             rid, keys))
    maybe0 = maybe_raw[0]
    maybe_any = maybe_raw[0]
    for m in maybe_raw[1:]:
        maybe_any = maybe_any | m
    b = bucket_of(cfg, keys)

    # ---- lane masks -----------------------------------------------------
    putk = keep & is_put
    upd = flook & putk                    # put: in-place value update
    fresh_put = putk & ~flook             # put: fresh insert
    delk = keep & is_del
    dfound = flook & delk
    maybe_del = maybe_any & delk
    free_d = dfound & ~maybe_del          # delete: free the tier-0 slot
    tomb_old = dfound & maybe_del         # delete: tombstone existing slot
    tomb_fresh = maybe_del & ~dfound      # delete: tombstone takes a slot

    # ---- allocation (delete's frees are visible to its own tombstones) --
    fast_keys = state.keys[0].at[
        jnp.where(free_d, fslot, nf)].set(-1, mode="drop")
    want = fresh_put | tomb_fresh
    new_slots = alloc_slots(fast_keys, want)
    ins_ok = want & (new_slots >= 0)

    # ---- pool writes ----------------------------------------------------
    upd_tgt = jnp.where(upd, fslot, nf)
    fast_vals = state.vals[0].at[upd_tgt].set(vals, mode="drop")
    fast_ver = state.fast_ver.at[upd_tgt].set(
        jnp.abs(state.fast_ver[jnp.clip(fslot, 0)]) + 1, mode="drop")
    ins_put = ins_ok & fresh_put
    ptgt = jnp.where(ins_put, new_slots, nf)
    fast_keys = fast_keys.at[ptgt].set(keys, mode="drop")
    fast_vals = fast_vals.at[ptgt].set(vals, mode="drop")
    fast_ver = fast_ver.at[ptgt].set(1, mode="drop")
    tomb_ok = tomb_old | (tomb_fresh & ins_ok)
    ttgt = jnp.where(tomb_ok, jnp.where(tomb_old, fslot, new_slots), nf)
    fast_keys = fast_keys.at[ttgt].set(keys, mode="drop")
    fast_ver = fast_ver.at[ttgt].set(-1, mode="drop")

    # ---- ONE incremental index update for both mutating lanes -----------
    dropm = jnp.zeros((nf,), bool).at[
        jnp.where(free_d, fpos, nf)].set(True, mode="drop")
    fidx_keys, fidx_slots = merge_index_update(
        state.idx_keys[0], state.idx_slots[0], dropm, keys, new_slots,
        ins_ok)

    # ---- bucket stats (boundary 0) --------------------------------------
    bucket_fast = state.bucket_fast.at[
        jnp.where(ins_ok, b, nb)].add(1, mode="drop")
    bucket_fast = bucket_fast.at[jnp.where(free_d, b, nb)].add(-1,
                                                               mode="drop")
    bucket_overlap = state.bucket_overlap.at[
        jnp.where(maybe0 & ins_put, b, nb)].add(1, mode="drop")

    # ---- get lane (reads the PRE-op pools: kinds are exclusive) ---------
    g = valid & is_get
    fhit = flook & g & ~tomb
    searching = g & ~flook               # tombstone hides lower copies
    hit_list, probe_list, tier_vals = [], [], []
    probe_cnt = jnp.zeros((), jnp.int32)
    fp_cnt = jnp.zeros((), jnp.int32)
    cnt = lambda m: jnp.sum(m.astype(jnp.int32))
    for t in range(1, n_tiers):
        maybe_t = maybe_raw[t - 1] & searching
        sslot, sfound = sorted_lookup(state.idx_keys[t],
                                      state.idx_slots[t], keys)
        if state.tombs:
            ltomb = state.tombs[t - 1][jnp.clip(sslot, 0)]
        else:
            ltomb = jnp.zeros_like(sfound)
        hit_t = sfound & maybe_t & ~ltomb
        tombhit_t = sfound & maybe_t & ltomb
        probe_cnt = probe_cnt + cnt(searching)
        fp_cnt = fp_cnt + cnt(maybe_t & ~sfound)
        hit_list.append(hit_t)
        probe_list.append(maybe_t)
        tier_vals.append(state.vals[t][jnp.clip(sslot, 0)])
        searching = searching & ~(hit_t | tombhit_t)
    fvals = state.vals[0][jnp.clip(fslot, 0)]
    out_vals = jnp.zeros_like(fvals)
    source = jnp.full(keys.shape, -1, jnp.int32)
    shit_any = jnp.zeros_like(fhit)
    for t in range(n_tiers - 1, 0, -1):
        out_vals = jnp.where(hit_list[t - 1][:, None],
                             tier_vals[t - 1], out_vals)
        source = jnp.where(hit_list[t - 1], t, source).astype(jnp.int32)
        shit_any = shit_any | hit_list[t - 1]
    out_vals = jnp.where(fhit[:, None], fvals, out_vals)
    source = jnp.where(fhit, 0, source).astype(jnp.int32)
    found = fhit | shit_any

    # ---- tracker --------------------------------------------------------
    trk_locs = jnp.where(shit_any, 1, 0).astype(jnp.int8)
    trk_mask = putk | (g & found)
    with jax.named_scope("tracker"):
        if backend == "reference":
            trk = tracker.access_batched(state.tracker, keys, trk_locs,
                                         trk_mask)
        else:
            from repro.kernels.clock_update.ops import tracker_access
            trk = tracker_access(state.tracker, keys, trk_locs, trk_mask,
                                 backend=backend, interpret=interpret)

    # ---- counters -------------------------------------------------------
    n_put = cnt(putk)
    zero = jnp.zeros((), jnp.int32)
    hits_inc = jnp.stack([cnt(fhit)] + [cnt(h) for h in hit_list])
    reads_inc = jnp.stack([cnt(fhit)] + [cnt(m) for m in probe_list])
    writes_inc = jnp.stack([n_put] + [zero] * (n_tiers - 1))
    ctr = state.ctr._replace(
        puts=state.ctr.puts + n_put,
        gets=state.ctr.gets + cnt(g),
        hits=state.ctr.hits + hits_inc,
        misses=state.ctr.misses + cnt(g & ~found),
        reads=state.ctr.reads + reads_inc,
        writes=state.ctr.writes + writes_inc,
        bloom_probes=state.ctr.bloom_probes + probe_cnt,
        bloom_fps=state.ctr.bloom_fps + fp_cnt,
    )
    state = state.update(
        fast_keys=fast_keys, fast_vals=fast_vals, fast_ver=fast_ver,
        fidx_keys=fidx_keys, fidx_slots=fidx_slots,
        bucket_fast=bucket_fast, bucket_overlap=bucket_overlap,
        tracker=trk, ctr=ctr)
    return state, out_vals, found, source


def consolidate_indexes(state: TierState) -> TierState:
    """Full-rebuild fallback: re-derive every sorted tier index from the
    pools (restores canonical pad-entry slots; live entries are already
    exact)."""
    idx = [build_sorted_index(k) for k in state.keys]
    ctr = state.ctr._replace(
        consolidations=state.ctr.consolidations + 1)
    return state._replace(idx_keys=tuple(k for k, _ in idx),
                          idx_slots=tuple(s for _, s in idx), ctr=ctr)


# ---------------------------------------------- single-kind conveniences

def put_batch(state: TierState, cfg: TierConfig, keys: jax.Array,
              vals: jax.Array, valid: jax.Array) -> TierState:
    """Insert/update a batch (static-kind specialization of the masked
    pass; XLA folds the dead lanes away)."""
    state, _, _, _ = apply_point_ops(state, cfg, keys, vals, valid,
                                     is_put=True, is_get=False, is_del=False)
    return state


def get_batch(state: TierState, cfg: TierConfig, keys: jax.Array,
              valid: jax.Array) -> tuple[TierState, jax.Array, jax.Array,
                                         jax.Array]:
    """Returns (state', vals, found, source), source = serving tier
    index (0 = fast slab), -1 = miss."""
    vals = jnp.zeros((keys.shape[0], state.vals[0].shape[1]),
                     state.vals[0].dtype)
    return apply_point_ops(state, cfg, keys, vals, valid,
                           is_put=False, is_get=True, is_del=False)


def delete_batch(state: TierState, cfg: TierConfig, keys: jax.Array,
                 valid: jax.Array) -> TierState:
    """Client deletes (paper §6)."""
    vals = jnp.zeros((keys.shape[0], state.vals[0].shape[1]),
                     state.vals[0].dtype)
    state, _, _, _ = apply_point_ops(state, cfg, keys, vals, valid,
                                     is_put=False, is_get=False, is_del=True)
    return state


def _scan_windows(state: TierState, lo: jax.Array, take: int) -> tuple:
    """The merged-scan core shared by ``scan`` and ``scan_lane_counts``:
    the next ``take`` index entries >= ``lo`` from EACH tier, with
    tombstoned entries and upper-tier-shadowed lower entries masked to
    PADKEY.  Returns one key window per tier, hottest first."""
    ar = jnp.arange(take)
    wins = []
    for t in range(len(state.keys)):
        ik, isl = state.idx_keys[t], state.idx_slots[t]
        start = jnp.searchsorted(ik, lo)
        pos = jnp.clip(start + ar, 0, ik.shape[0] - 1)
        k = jnp.where(start + ar < ik.shape[0], ik[pos], PADKEY)
        if t == 0:
            dead = state.fast_ver[jnp.clip(isl[pos], 0)] < 0
        else:
            if state.tombs:
                dead = state.tombs[t - 1][jnp.clip(isl[pos], 0)]
            else:
                dead = jnp.zeros(k.shape, bool)
            # drop keys shadowed by ANY upper-tier copy (incl. their
            # tombstones: an index entry shadows regardless)
            for u in range(t):
                _, shadowed = sorted_lookup(state.idx_keys[u],
                                            state.idx_slots[u], k)
                dead = dead | shadowed
        wins.append(jnp.where(dead, PADKEY, k))
    return tuple(wins)


def scan(state: TierState, lo: jax.Array, n: int) -> tuple[jax.Array,
                                                           jax.Array]:
    """Return up to ``n`` live keys >= lo in sorted order, merged across
    every tier (upper versions supersede lower; tombstones suppress)."""
    wins = _scan_windows(state, lo, n)   # over-fetch n per tier, merge
    allk = jnp.sort(jnp.concatenate(wins))
    keys = allk[:n]
    return keys, keys != PADKEY


def scan_lane_counts(state: TierState, starts: jax.Array, lens: jax.Array,
                     *, chunk: int) -> tuple[jax.Array, jax.Array]:
    """The scan lane's work: per lane, how many of the live keys >=
    ``starts[b]`` it returns (up to ``lens[b]``, window-bounded by
    ``chunk`` index entries per tier), in all (``i32[B]``) and per tier
    (``i32[B, T]``).  Reads only the indexes, ``fast_ver`` and
    ``tombs``, and returns counts alone, so it can sit in a branch
    without carrying pool state through it."""
    n_tiers = len(state.keys)

    def one(lo, ln):
        wins = _scan_windows(state, lo, chunk)
        keys = jnp.concatenate(wins)
        tier_of = jnp.concatenate(
            [jnp.full((chunk,), t, jnp.int32) for t in range(n_tiers)])
        order = jnp.argsort(keys)
        keys, tier_of = keys[order], tier_of[order]
        live = keys != PADKEY
        sel = live & (jnp.cumsum(live.astype(jnp.int32)) <= ln)
        per_tier = jnp.stack(
            [jnp.sum((sel & (tier_of == t)).astype(jnp.int32))
             for t in range(n_tiers)])
        return jnp.sum(sel.astype(jnp.int32)), per_tier

    return jax.vmap(one)(starts, lens)


def count_scans(state: TierState, valid: jax.Array, per_tier: jax.Array
                ) -> TierState:
    """Charge a scan batch's I/O: ``valid`` lanes to ``scans``, every
    returned object (``per_tier``, ``i32[B, T]``) a read on its tier;
    run-structured-tier scan reads are sequential (runs are key-sorted),
    so they also land in that tier's ``scan_reads`` entry for the cost
    model."""
    tier_tot = jnp.sum(per_tier, axis=0)        # i32[T]
    seq_tot = tier_tot.at[0].set(0)             # tier-0 reads are random
    ctr = state.ctr._replace(
        scans=state.ctr.scans + jnp.sum(valid.astype(jnp.int32)),
        scan_objs=state.ctr.scan_objs + jnp.sum(tier_tot),
        reads=state.ctr.reads + tier_tot,
        scan_reads=state.ctr.scan_reads + seq_tot,
    )
    return state._replace(ctr=ctr)


def scan_batch(state: TierState, cfg: TierConfig, starts: jax.Array,
               lens: jax.Array, valid: jax.Array, *, chunk: int
               ) -> tuple[TierState, jax.Array]:
    """Batched bounded range scans (YCSB-E) over the merged sorted indexes.

    Per lane: up to ``lens[b]`` live keys >= ``starts[b]`` in sorted order,
    window-bounded by ``chunk`` index entries per tier.  Returns
    ``(state', n_live)`` where ``n_live[b]`` counts the keys the scan
    returned (also totaled in ``scan_objs``), charged as ``count_scans``
    says.
    """
    ln = jnp.where(valid, jnp.maximum(lens, 0), 0)
    n_live, per_tier = scan_lane_counts(state, starts, ln, chunk=chunk)
    return count_scans(state, valid, per_tier), n_live


# ------------------------------------------------------- host-side export

def counters_dict(ctr: Counters, partitioned: bool = False) -> dict:
    """Host-side counter export shared by every facade: all pair-era
    scalar keys (bit-identical values) plus ``*_by_tier`` vector keys.
    With ``partitioned=True`` every leaf has a leading partition axis
    and each value becomes a per-partition list."""
    import numpy as np
    host = jax.device_get(ctr)
    vec = {"hits", "reads", "writes", "comp_reads", "scan_reads",
           "comp_by_boundary"}

    def ints(a):
        return [ints(row) for row in a] if a.ndim > 1 else \
            [int(x) for x in a]

    d = {}
    for k, v in host._asdict().items():
        a = np.asarray(v)
        if k in vec:
            key = k if k == "comp_by_boundary" else k + "_by_tier"
            d[key] = ints(a)
        else:
            d[k] = ints(a) if partitioned else int(a)

    def cast(a):
        a = np.asarray(a)
        return [int(x) for x in a] if partitioned else int(a)

    hits = np.asarray(host.hits)
    reads = np.asarray(host.reads)
    writes = np.asarray(host.writes)
    d["hits_fast"] = cast(hits[..., 0])
    d["hits_slow"] = cast(hits[..., 1:].sum(axis=-1))
    d["fast_reads"] = cast(reads[..., 0])
    d["slow_reads"] = cast(reads[..., 1:].sum(axis=-1))
    d["fast_writes"] = cast(writes[..., 0])
    d["slow_writes"] = cast(writes[..., 1:].sum(axis=-1))
    d["comp_reads"] = cast(np.asarray(host.comp_reads).sum(axis=-1))
    d["scan_reads"] = cast(np.asarray(host.scan_reads).sum(axis=-1))
    return d
