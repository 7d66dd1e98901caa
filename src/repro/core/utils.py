"""Shared helpers for the PrismDB core: hashing, sorted-index ops, masking.

Conventions used across ``repro.core``:
  * keys are int32 in the domain ``[0, key_space)``
  * ``EMPTY  = -1``          marks a free pool slot
  * ``PADKEY = 2**31 - 1``   pads sorted indices (sorts after every real key)
  * every function is jit-safe with static shapes; variable-size sets are
    carried as ``(array, mask)`` pairs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EMPTY = jnp.int32(-1)
PADKEY = jnp.int32(2**31 - 1)

# Knuth multiplicative hashing constants (distinct streams per use-site).
_HASH_MULS = (2654435761, 2246822519, 3266489917, 668265263, 374761393)


def hash_u32(keys: jax.Array, salt: int = 0) -> jax.Array:
    """Deterministic 32-bit mix of int32 keys (xorshift-multiply)."""
    x = keys.astype(jnp.uint32)
    x = x ^ jnp.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF)
    x = x * jnp.uint32(_HASH_MULS[salt % len(_HASH_MULS)])
    x = x ^ (x >> 15)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    return x


def hash_mod(keys: jax.Array, n: int, salt: int = 0) -> jax.Array:
    """Hash keys into ``[0, n)``. ``n`` need not be a power of two."""
    return (hash_u32(keys, salt) % jnp.uint32(n)).astype(jnp.int32)


def mix32(x: jax.Array, salt: int = 0) -> jax.Array:
    """Splitmix-style 32-bit finalizer (murmur3 fmix32 constants): every
    input bit avalanches into every output bit.  Stronger than
    ``hash_u32``'s xorshift-multiply -- used where aliasing would
    CONCENTRATE load (partition routing: a skewed tenant whose hot keys
    collide onto one partition turns shared-nothing scaling into a
    single-partition hotspot)."""
    x = x.astype(jnp.uint32) ^ jnp.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def part_of_key(keys: jax.Array, n_parts: int, salt: int = 4) -> jax.Array:
    """Owning partition of each key: splitmix-mixed hash mod ``n_parts``.

    The SINGLE source of truth for key->partition placement: the vmapped
    ``route_batch`` and the mesh-sharded device-side exchange
    (``distributed.collectives.exchange_keys``) must agree bit-for-bit,
    or a key routed under one path is unreachable under the other."""
    return (mix32(keys, salt) % jnp.uint32(n_parts)).astype(jnp.int32)


def pack_buckets(keys: jax.Array, part: jax.Array, n: int, cap: int,
                 valid: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter a batch into ``[n, cap]`` fixed-capacity per-destination
    buckets, preserving in-batch order within each bucket (stable sort).

    Returns ``(buckets, bucket_valid, dropped)``: overflow beyond ``cap``
    in one bucket is counted in the PER-DESTINATION ``dropped`` i32[n]
    vector, never silently lost.  ``valid=None`` treats every lane live;
    invalid lanes land nowhere and count nowhere."""
    b = keys.shape[0]
    if valid is None:
        valid = jnp.ones((b,), bool)
    # invalid lanes sort to the end of an out-of-range group: they can
    # neither occupy a bucket slot nor inflate a real group's ranks
    part = jnp.where(valid, part, n)
    order = jnp.argsort(part)                   # stable: in-batch order
    keys_s, part_s = keys[order], part[order]
    rank = jnp.arange(b) - jnp.searchsorted(part_s, part_s, side="left")
    out = jnp.full((n, cap), -1, jnp.int32)
    ok = rank < cap
    tgt = jnp.where(ok, part_s, n)              # overflow scatters away
    out = out.at[tgt, jnp.clip(rank, 0, cap - 1)].set(keys_s, mode="drop")
    dropped = jnp.zeros((n,), jnp.int32).at[part_s].add(
        (~ok).astype(jnp.int32), mode="drop")
    return out, out >= 0, dropped


def sorted_lookup(index_keys: jax.Array, index_vals: jax.Array,
                  query: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Look up ``query`` keys in a PADKEY-padded sorted index.

    Returns ``(vals, found)``; ``vals`` is garbage where ``found`` is False.
    """
    pos, found = sorted_position(index_keys, query)
    return index_vals[pos], found


def sorted_position(index_keys: jax.Array,
                    query: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(pos, found)``: each query's index position (clamped into the
    index) and whether the key sits there."""
    pos = jnp.searchsorted(index_keys, query)
    pos = jnp.clip(pos, 0, index_keys.shape[0] - 1)
    return pos, index_keys[pos] == query


def build_sorted_index(pool_keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(sorted_keys, slot_of_sorted) over a pool; free slots sort to the end.

    Full O(N log N) rebuild.  Hot paths maintain the index incrementally
    with ``merge_index_update``; this survives as the init path, the oracle
    the property tests compare against, and the periodic consolidation
    fallback (``EngineConfig.consolidate_every``).
    """
    k = jnp.where(pool_keys < 0, PADKEY, pool_keys)
    order = jnp.argsort(k)
    return k[order], order.astype(jnp.int32)


def merge_index_update(idx_keys: jax.Array, idx_slots: jax.Array,
                       drop: jax.Array, ins_keys: jax.Array,
                       ins_slots: jax.Array, ins_valid: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
    """Incremental sorted-index maintenance: merge a batch update into a
    PADKEY-padded sorted index.

    ``drop`` is bool[N] over INDEX POSITIONS: the entries it marks become
    pads.  ``ins_*`` is a static-width batch of (key, slot) pairs to
    insert as live entries.  Precondition (all op paths satisfy it):
    inserted keys are unique within the batch and not live in the index
    after drops are applied.

    One sort of the N + B (key, slot) pairs.  A sort is the cheap way to
    move every index entry on the TPU: a merge that scatters each entry
    to its new position costs more than a sort of the whole index (XLA's
    TPU scatter sorts its indices before it writes).  The result's live
    prefix is bit-identical to ``build_sorted_index`` of the updated
    pool; pad-entry slot values are
    unspecified (nothing reads them: lookups and scans mask on
    ``key != PADKEY`` before using a slot) but are a function of the
    updated entries only, since pads sort by slot too.
    """
    n = idx_keys.shape[0]
    keys = jnp.concatenate([jnp.where(drop, PADKEY, idx_keys),
                            jnp.where(ins_valid, ins_keys, PADKEY)])
    slots = jnp.concatenate([idx_slots, ins_slots.astype(jnp.int32)])
    keys, slots = jax.lax.sort((keys, slots), num_keys=2)
    return keys[:n], slots[:n]


def splice_index_range(idx_keys: jax.Array, idx_slots: jax.Array,
                       start: jax.Array, n_drop: jax.Array,
                       ins_keys: jax.Array, ins_slots: jax.Array,
                       n_ins: jax.Array, max_drop: int
                       ) -> tuple[jax.Array, jax.Array]:
    """Replace the index entries ``[start, start + n_drop)`` with the
    first ``n_ins`` entries of the sorted batch ``ins_*``.

    The compaction form of ``merge_index_update``: a merge window drops
    every live key of one key range -- a contiguous stretch of the sorted
    index -- and writes its merged keys back into the same range, so the
    update is a splice: the batch lands at ``start`` and the tail shifts
    by ``n_drop - n_ins``, a few streaming passes over the index, far
    cheaper than a sort of it.  ``n_drop <= max_drop`` (static).  Same
    live prefix as ``merge_index_update``; pad-entry slots unspecified.
    """
    n, m = idx_keys.shape[0], ins_keys.shape[0]
    pad = max(m, max_drop)
    j = jnp.arange(n + m, dtype=jnp.int32)
    fresh = jnp.arange(m, dtype=jnp.int32) < n_ins

    def splice(x, ins, fill):
        # work on n + m entries, so the batch window never clamps
        head = jnp.concatenate([x, jnp.full((m,), fill, x.dtype)])
        ext = jnp.concatenate([jnp.full((pad,), fill, x.dtype), x,
                               jnp.full((pad + m,), fill, x.dtype)])
        tail = jax.lax.dynamic_slice(ext, (pad + n_drop - n_ins,), (n + m,))
        out = jnp.where(j < start, head, tail)
        win = jax.lax.dynamic_slice(out, (start,), (m,))
        out = jax.lax.dynamic_update_slice(out, jnp.where(fresh, ins, win),
                                           (start,))
        return out[:n]

    return (splice(idx_keys, ins_keys, PADKEY),
            splice(idx_slots, ins_slots, jnp.int32(0)))


def alloc_slots(pool_keys: jax.Array, want_mask: jax.Array) -> jax.Array:
    """Allocate one free slot per True in ``want_mask`` (static size).

    Returns int32 slots, -1 where ``want_mask`` is False or the pool is full.
    Deterministic: lowest-numbered free slots first.  The r-th free slot
    is found by binary search over the running count of free slots, not
    by ``jnp.nonzero``, whose pool-length scatter-add is far slower on
    the TPU.
    """
    free = pool_keys < 0
    n_free_upto = jnp.cumsum(free.astype(jnp.int32))
    req_rank = jnp.cumsum(want_mask.astype(jnp.int32)) - 1
    slots = jnp.searchsorted(n_free_upto, req_rank + 1).astype(jnp.int32)
    # not enough free slots -> -1
    return jnp.where(want_mask & (req_rank < n_free_upto[-1]), slots, -1)


def dedupe_keep_last(keys: jax.Array, valid: jax.Array) -> jax.Array:
    """Mask that keeps only the LAST occurrence of each valid key.

    Batched writes may repeat a key; the last write wins (RocksDB semantics).
    """
    n = keys.shape[0]
    k = jnp.where(valid, keys, PADKEY)
    idx = jnp.arange(n, dtype=jnp.int32)
    # stable sort by key; within equal keys order is ascending index
    order = jnp.argsort(k, stable=True)
    ks, ix = k[order], idx[order]
    is_last = jnp.concatenate([ks[:-1] != ks[1:], jnp.array([True])])
    keep_sorted = is_last & (ks != PADKEY)
    keep = jnp.zeros(n, dtype=bool).at[ix].set(keep_sorted)
    return keep & valid


def segment_in_range(sorted_keys: jax.Array, lo: jax.Array, hi: jax.Array,
                     cap: int) -> tuple[jax.Array, jax.Array]:
    """Positions of sorted_keys in [lo, hi), capped at ``cap``.

    Returns ``(positions[cap], mask[cap])``. Positions are clipped in-bounds;
    use the mask. Counting is exact; the slice is truncated if > cap.
    """
    start = jnp.searchsorted(sorted_keys, lo)
    end = jnp.searchsorted(sorted_keys, hi)
    pos = start + jnp.arange(cap, dtype=start.dtype)
    mask = pos < end
    pos = jnp.clip(pos, 0, sorted_keys.shape[0] - 1)
    return pos.astype(jnp.int32), mask
