"""Incremental sorted-index maintenance vs the full-rebuild oracle.

The hot paths (put/delete/compaction) maintain ``(fidx_keys, fidx_slots)``
and ``(sidx_keys, sidx_slots)`` with ``merge_index_update``;
``build_sorted_index`` survives as the oracle.  Equivalence contract:
  * the key arrays are BIT-IDENTICAL (PADKEY padding included);
  * slot entries agree wherever the key is live (pad-entry slots are
    explicitly unspecified -- nothing reads a slot without checking the
    key first).
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:                                   # property tests need hypothesis;
    from hypothesis import given, settings      # everything else runs
    from hypothesis import strategies as st     # without it
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import PrismDB, TierConfig, engine, tiers
from repro.core.utils import (PADKEY, alloc_slots, build_sorted_index,
                              merge_index_update, splice_index_range)

CFG = TierConfig(key_space=512, fast_slots=64, slow_slots=1024,
                 value_width=1, max_runs=32, run_size=32,
                 bloom_bits_per_run=1 << 10, tracker_slots=256,
                 n_buckets=16, pin_threshold=0.1)


def canon(idx_keys, idx_slots):
    """Canonical index view: pad-entry slots are unspecified -> mask them."""
    k = np.asarray(idx_keys)
    s = np.asarray(idx_slots)
    return k, np.where(k != int(PADKEY), s, -1)


def assert_index_matches_oracle(db: PrismDB):
    st_ = db.state
    for pool, ik, isl in ((st_.fast_keys, st_.fidx_keys, st_.fidx_slots),
                          (st_.slow_keys, st_.sidx_keys, st_.sidx_slots)):
        ok, osl = build_sorted_index(pool)
        gk, gs = canon(ik, isl)
        ek, es = canon(ok, osl)
        np.testing.assert_array_equal(gk, ek)
        np.testing.assert_array_equal(gs, es)


# ------------------------------------------------------- primitive-level

def test_merge_update_insert_only():
    pool = jnp.asarray([-1, 7, -1, 3], jnp.int32)
    ik, isl = build_sorted_index(pool)
    out_k, out_s = merge_index_update(
        ik, isl, jnp.zeros(4, bool),
        jnp.asarray([5, 9], jnp.int32), jnp.asarray([0, 2], jnp.int32),
        jnp.asarray([True, True]))
    new_pool = pool.at[0].set(5).at[2].set(9)
    ek, es = build_sorted_index(new_pool)
    np.testing.assert_array_equal(*map(np.asarray, (out_k, ek)))
    gk, gs = canon(out_k, out_s)
    np.testing.assert_array_equal(gs, canon(ek, es)[1])


def test_merge_update_drop_only():
    pool = jnp.asarray([4, 7, 2, 3], jnp.int32)
    ik, isl = build_sorted_index(pool)
    drop = jnp.asarray([False, True, False, True])[isl]   # slots 1, 3
    out_k, out_s = merge_index_update(
        ik, isl, drop, jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
        jnp.zeros(2, bool))
    ek, es = build_sorted_index(jnp.asarray([4, -1, 2, -1], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(ek))
    np.testing.assert_array_equal(canon(out_k, out_s)[1], canon(ek, es)[1])


def test_merge_update_slot_reuse():
    """A dropped slot immediately reused by an insert (the compaction
    demote->promote pattern) must stay consistent."""
    pool = jnp.asarray([4, 7, 2], jnp.int32)
    ik, isl = build_sorted_index(pool)
    drop = jnp.asarray([False, True, False])[isl]          # slot 1
    out_k, out_s = merge_index_update(
        ik, isl, drop, jnp.asarray([5], jnp.int32),
        jnp.asarray([1], jnp.int32), jnp.asarray([True]))
    ek, es = build_sorted_index(jnp.asarray([4, 5, 2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(ek))
    np.testing.assert_array_equal(canon(out_k, out_s)[1], canon(ek, es)[1])


def test_merge_update_random_vs_oracle():
    """Seeded randomized primitive check (drops + inserts + pad lanes)."""
    rng = np.random.default_rng(7)
    n, b = 48, 8
    for _ in range(50):
        nlive = int(rng.integers(0, n))
        pool = np.full(n, -1, np.int32)
        slots = rng.choice(n, nlive, replace=False)
        pool[slots] = rng.choice(4000, nlive, replace=False).astype(np.int32)
        ik, isl = build_sorted_index(jnp.asarray(pool))
        ndrop = int(rng.integers(0, nlive + 1))
        dsl = rng.choice(slots, ndrop, replace=False) if ndrop else []
        drop = np.zeros(n, bool)
        drop[list(dsl)] = True
        new_pool = pool.copy()
        new_pool[list(dsl)] = -1
        free = np.flatnonzero(new_pool < 0)
        nins = int(rng.integers(0, min(b, len(free)) + 1))
        ins_s = rng.choice(free, nins, replace=False)
        ins_k = rng.choice(np.arange(5000, 9000), nins,
                           replace=False).astype(np.int32)
        new_pool[ins_s] = ins_k
        lanes_k = np.zeros(b, np.int32)
        lanes_s = np.zeros(b, np.int32)
        lanes_v = np.zeros(b, bool)
        lanes_k[:nins], lanes_s[:nins], lanes_v[:nins] = ins_k, ins_s, True
        perm = rng.permutation(b)
        out_k, out_s = merge_index_update(
            ik, isl, jnp.asarray(drop)[isl], jnp.asarray(lanes_k[perm]),
            jnp.asarray(lanes_s[perm]), jnp.asarray(lanes_v[perm]))
        ek, es = build_sorted_index(jnp.asarray(new_pool))
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(ek))
        np.testing.assert_array_equal(canon(out_k, out_s)[1],
                                      canon(ek, es)[1])


def test_splice_range_random_vs_oracle():
    """Seeded compaction-shaped updates: every live key of a key range
    [lo, hi) drops out and fresh keys of the same range take its place
    (fewer, as many, or more than were dropped; up to a full pool)."""
    rng = np.random.default_rng(11)
    n, b, max_drop = 48, 12, 16
    for _ in range(60):
        nlive = int(rng.integers(0, n + 1))
        pool = np.full(n, -1, np.int32)
        slots = rng.choice(n, nlive, replace=False)
        pool[slots] = rng.choice(4000, nlive, replace=False).astype(np.int32)
        ik, isl = build_sorted_index(jnp.asarray(pool))
        live = np.sort(pool[pool >= 0])
        start = int(rng.integers(0, nlive + 1))
        ndrop = int(rng.integers(0, min(max_drop, nlive - start) + 1))
        lo = int(live[start]) if start < nlive else 4000
        hi = int(live[start + ndrop]) if start + ndrop < nlive else 4000
        lo = int(rng.integers(int(live[start - 1]) + 1, lo + 1)) \
            if start > 0 else 0
        new_pool = np.where((pool >= lo) & (pool < hi), -1, pool)
        free = np.flatnonzero(new_pool < 0)
        cands = np.setdiff1d(np.arange(lo, hi), live)
        nins = int(rng.integers(0, min(b, len(free), len(cands)) + 1))
        ins_k = np.sort(rng.choice(cands, nins, replace=False))
        ins_s = rng.choice(free, nins, replace=False)
        new_pool[ins_s] = ins_k
        lanes_k = np.full(b, int(PADKEY), np.int32)
        lanes_s = np.zeros(b, np.int32)
        lanes_k[:nins], lanes_s[:nins] = ins_k, ins_s
        out_k, out_s = splice_index_range(
            ik, isl, jnp.int32(start), jnp.int32(ndrop),
            jnp.asarray(lanes_k), jnp.asarray(lanes_s), jnp.int32(nins),
            max_drop)
        ek, es = build_sorted_index(jnp.asarray(new_pool))
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(ek))
        np.testing.assert_array_equal(canon(out_k, out_s)[1],
                                      canon(ek, es)[1])


def test_alloc_slots_lowest_free_first():
    pool = jnp.asarray([3, -1, 5, -1, -1, 8, -1], jnp.int32)
    want = jnp.asarray([True, False, True, True, True, True])
    got = np.asarray(alloc_slots(pool, want))
    np.testing.assert_array_equal(got, [1, -1, 3, 4, 6, -1])


# ------------------------------------------------------------ store-level

def _run_op_sequence(ops):
    """Drive put/delete/get batches (with duplicate keys: last write wins)
    and compactions through the facade; after EVERY step both maintained
    indexes must match the rebuild oracle."""
    db = PrismDB(CFG, seed=3)
    val = 0.0
    for op, keys in ops:
        karr = np.asarray(keys, np.int32)
        if op == "put":
            val += 1.0
            db.put(karr, vals=jnp.full((len(keys), 1), val))
        elif op == "del":
            db.delete(karr)
        else:
            db.get(karr)
        assert_index_matches_oracle(db)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(st.lists(
        st.tuples(st.sampled_from(["put", "del", "get"]),
                  st.lists(st.integers(0, 300), min_size=1, max_size=24)),
        min_size=2, max_size=12))
    def test_index_matches_oracle_random_ops(ops):
        _run_op_sequence(ops)
else:
    def test_index_matches_oracle_random_ops():
        rng = np.random.default_rng(5)
        ops = [(("put", "del", "get")[int(rng.integers(0, 3))],
                rng.integers(0, 300, size=int(rng.integers(1, 24))).tolist())
               for _ in range(24)]
        _run_op_sequence(ops)


def test_index_matches_oracle_through_compactions():
    """Overflow the fast tier so watermark compactions (demote + promote +
    run rewrites) run, then delete across tiers; the incrementally
    maintained indexes must match the oracle at every observation point."""
    db = PrismDB(CFG, seed=0)
    rng = np.random.default_rng(2)
    for i in range(12):
        ks = rng.integers(0, CFG.key_space, size=48).astype(np.int32)
        db.put(ks)
        assert_index_matches_oracle(db)
    assert db.counters["compactions"] > 0
    db.delete(rng.integers(0, CFG.key_space, size=32).astype(np.int32))
    assert_index_matches_oracle(db)
    db.get(rng.integers(0, CFG.key_space, size=64).astype(np.int32))
    assert_index_matches_oracle(db)


def test_duplicate_key_overwrite_order():
    """A batch repeating a key keeps only the LAST write (RocksDB
    semantics) and the index holds exactly one live entry for it."""
    db = PrismDB(CFG, seed=0)
    keys = np.asarray([5, 9, 5, 5], np.int32)
    vals = jnp.asarray([[1.0], [2.0], [3.0], [4.0]])
    db.put(keys, vals=vals)
    assert_index_matches_oracle(db)
    got, found, _ = db.get(np.asarray([5, 9], np.int32))
    assert bool(jnp.all(found))
    assert float(got[0, 0]) == 4.0 and float(got[1, 0]) == 2.0
    s = db.state
    assert int(np.sum(np.asarray(s.fidx_keys) == 5)) == 1


def test_consolidation_keeps_oracle_equivalence():
    """The periodic full rebuild (consolidate_every) only re-canonicalizes
    pad slots: steps with and without a consolidation tick all stay
    oracle-exact on live entries, and the counter records each rebuild."""
    db = PrismDB(CFG, seed=1, consolidate_every=4)
    rng = np.random.default_rng(9)
    for i in range(9):
        db.put(rng.integers(0, CFG.key_space, size=20).astype(np.int32))
        assert_index_matches_oracle(db)
    assert db.counters["consolidations"] == 9 // 4
