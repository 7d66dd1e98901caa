"""Clock-based popularity tracker (PrismDB §4.3, §6).

The paper's tracker is a concurrent hash map: key -> 1 byte (2 clock bits +
1 location bit), sized to ~10-20% of the key space.  Keys are inserted with
clock 0 and bumped to 3 on a subsequent access; eviction approximates CLOCK.

TPU adaptation (DESIGN.md §5): a direct-mapped hash table with
clock-protected overwrite --

  * hit        -> clock = 3                       (paper: re-access sets 3)
  * empty slot -> insert with clock 0             (paper: insert at 0)
  * collision  -> resident clock > 0: decrement   (the CLOCK second chance)
                  resident clock == 0: evict, insert new key at clock 0

This keeps updates O(1)/vectorizable (no global clock hand) while preserving
the property the mapper consumes: the clock-value histogram of resident keys
tracks the recent access-frequency distribution.  ``access_seq`` is the exact
ordered reference; ``access_batched`` is the vectorized fast path (identical
on batches with no inter-key slot collisions -- tested).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.utils import hash_mod

CLOCK_MAX = 3  # 2-bit clock
LOC_FAST = jnp.int8(0)
LOC_SLOW = jnp.int8(1)


class TrackerState(NamedTuple):
    keys: jax.Array   # int32[T], -1 = empty
    clock: jax.Array  # int8[T] in [0, 3]
    loc: jax.Array    # int8[T]  0=fast tier, 1=slow tier

    @property
    def capacity(self) -> int:
        return int(self.keys.shape[0])


def init(capacity: int) -> TrackerState:
    return TrackerState(
        keys=jnp.full((capacity,), -1, dtype=jnp.int32),
        clock=jnp.zeros((capacity,), dtype=jnp.int8),
        loc=jnp.zeros((capacity,), dtype=jnp.int8),
    )


def _slot(state: TrackerState, keys: jax.Array) -> jax.Array:
    return hash_mod(keys, state.capacity, salt=1)


def access_seq(state: TrackerState, keys: jax.Array, locs: jax.Array,
               valid: jax.Array) -> TrackerState:
    """Exact ordered semantics via lax.scan over the batch (reference path)."""
    slots = _slot(state, keys)

    def step(carry, x):
        tk, tc, tl = carry
        s, k, loc, v = x
        resident = tk[s] == k
        empty = tk[s] < 0
        protect = (~resident) & (~empty) & (tc[s] > 0)
        new_key = jnp.where(resident | protect, tk[s], k)
        new_clock = jnp.where(
            resident, jnp.int8(CLOCK_MAX),
            jnp.where(protect, tc[s] - 1, jnp.int8(0)))
        new_loc = jnp.where(resident | ~protect, loc, tl[s])
        tk = tk.at[s].set(jnp.where(v, new_key, tk[s]))
        tc = tc.at[s].set(jnp.where(v, new_clock, tc[s]))
        tl = tl.at[s].set(jnp.where(v, new_loc, tl[s]))
        return (tk, tc, tl), None

    (tk, tc, tl), _ = jax.lax.scan(
        step, (state.keys, state.clock, state.loc),
        (slots, keys, locs.astype(jnp.int8), valid))
    return TrackerState(tk, tc, tl)


def access_batched(state: TrackerState, keys: jax.Array, locs: jax.Array,
                   valid: jax.Array) -> TrackerState:
    """Vectorized batch update (the canonical semantics; the Pallas
    clock_update kernel implements exactly this).

    Per-slot aggregation over the batch:
      * any access matching the resident key -> clock = 3 (loc of the last
        matching access);
      * otherwise the LAST valid access targeting the slot is the insert
        candidate; resident entries with clock > 0 are protected (decay 1),
        empty or clock-0 slots take the candidate (clock 3 if the batch
        accessed that key >= 2 times, else 0 -- matching the ordered path).
    """
    n = keys.shape[0]
    t = state.capacity
    slots = jnp.where(valid, _slot(state, keys), t)
    sk = jnp.where(valid, keys, jnp.int32(-1))
    occ = jnp.sum((sk[None, :] == sk[:, None]) & valid[None, :], axis=1) \
        if n <= 512 else _occ_large(sk, valid)

    # group batch elements by slot (stable: batch order within a group)
    order = jnp.argsort(slots, stable=True)
    s_sorted = slots[order]
    seg_new = jnp.concatenate([jnp.array([True]),
                               s_sorted[1:] != s_sorted[:-1]])
    gid = jnp.cumsum(seg_new.astype(jnp.int32)) - 1

    res_key = state.keys[jnp.clip(s_sorted, 0, t - 1)]
    match = (keys[order] == res_key) & (s_sorted < t)
    j_idx = jnp.arange(n, dtype=jnp.int32)
    any_hit = jax.ops.segment_max(match.astype(jnp.int32), gid,
                                  num_segments=n) > 0
    last_match = jax.ops.segment_max(jnp.where(match, j_idx, -1), gid,
                                     num_segments=n)
    last_cand = jax.ops.segment_max(jnp.where(s_sorted < t, j_idx, -1), gid,
                                    num_segments=n)
    seg_slot = jax.ops.segment_min(jnp.where(s_sorted < t, s_sorted, t), gid,
                                   num_segments=n)

    # per-segment results (segments beyond the group count are inert: t)
    cand = order[jnp.clip(last_cand, 0)]
    hit_j = order[jnp.clip(last_match, 0)]
    sslot = jnp.clip(seg_slot, 0, t - 1)
    res_clock = state.clock[sslot].astype(jnp.int32)
    res_empty = state.keys[sslot] < 0
    protect = ~any_hit & ~res_empty & (res_clock > 0)
    insert = ~any_hit & (res_empty | (res_clock == 0))

    new_key = jnp.where(insert, keys[cand], state.keys[sslot])
    new_clock = jnp.where(
        any_hit, CLOCK_MAX,
        jnp.where(protect, res_clock - 1,
                  jnp.where(occ[cand] >= 2, CLOCK_MAX, 0))).astype(jnp.int8)
    new_loc = jnp.where(any_hit, locs[hit_j].astype(jnp.int8),
                        jnp.where(insert, locs[cand].astype(jnp.int8),
                                  state.loc[sslot]))

    live = (seg_slot < t) & (last_cand >= 0)
    tgt = jnp.where(live, seg_slot, t)
    tk = state.keys.at[tgt].set(new_key, mode="drop")
    tc = state.clock.at[tgt].set(new_clock, mode="drop")
    tl = state.loc.at[tgt].set(new_loc, mode="drop")
    return TrackerState(tk, tc, tl)


def _occ_large(sk: jax.Array, valid: jax.Array) -> jax.Array:
    """O(n log n) occurrence count for big batches (sort + segment sums)."""
    n = sk.shape[0]
    order = jnp.argsort(sk)
    s = sk[order]
    new_grp = jnp.concatenate([jnp.array([True]), s[1:] != s[:-1]])
    gid = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
    counts = jax.ops.segment_sum(jnp.ones(n, jnp.int32), gid, num_segments=n)
    occ_sorted = counts[gid]
    occ = jnp.zeros(n, jnp.int32).at[order].set(occ_sorted)
    return jnp.where(valid, occ, 0)


def lookup_clock(state: TrackerState, keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(clock, tracked) per key; untracked keys get clock 0 (-> coldness 1)."""
    slots = _slot(state, keys)
    tracked = state.keys[slots] == keys
    clock = jnp.where(tracked, state.clock[slots], jnp.int8(0))
    return clock, tracked


def set_location(state: TrackerState, keys: jax.Array, loc: jax.Array,
                 valid: jax.Array) -> TrackerState:
    """Update location bits after demotion/promotion (only if still tracked)."""
    slots = _slot(state, keys)
    hit = (state.keys[slots] == keys) & valid
    tgt = jnp.where(hit, slots, state.capacity)
    if jnp.ndim(loc) == 0:
        loc = jnp.full(keys.shape, loc, dtype=jnp.int8)
    return state._replace(loc=state.loc.at[tgt].set(loc.astype(jnp.int8),
                                                    mode="drop"))


def clock_histogram(state: TrackerState) -> jax.Array:
    """int32[4] histogram of clock values over resident tracked keys.

    This is the mapper's input distribution (paper Fig. 5).
    """
    resident = state.keys >= 0
    vals = jnp.where(resident, state.clock.astype(jnp.int32), 4)
    # four counting reductions, not a bincount (a scatter-add, far slower
    # on the TPU)
    return jnp.sum(vals[:, None] == jnp.arange(4, dtype=jnp.int32),
                   axis=0, dtype=jnp.int32)


def fast_fraction_of_tracked(state: TrackerState) -> jax.Array:
    """Fraction of tracked keys whose last access hit the fast tier.

    Drives read-triggered compaction detection (paper §5.3).
    """
    resident = state.keys >= 0
    n = jnp.maximum(jnp.sum(resident.astype(jnp.int32)), 1)
    fast = jnp.sum((resident & (state.loc == LOC_FAST)).astype(jnp.int32))
    return fast.astype(jnp.float32) / n.astype(jnp.float32)
