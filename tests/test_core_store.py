"""TieredStore + compaction: round trips, invariants, oracle property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                                   # property tests need hypothesis;
    from hypothesis import given, settings      # everything else runs
    from hypothesis import strategies as st     # without it
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import PrismDB, TierConfig, bloom, compaction, msc, tiers

CFG = TierConfig(key_space=1 << 13, fast_slots=256, slow_slots=1 << 12,
                 value_width=2, max_runs=64, run_size=128,
                 bloom_bits_per_run=1 << 12, tracker_slots=1 << 10,
                 n_buckets=32, pin_threshold=0.1)


def mkdb(**kw):
    return PrismDB(CFG, seed=0, **kw)


def test_put_get_roundtrip():
    db = mkdb()
    keys = np.arange(0, 200, dtype=np.int32)
    db.put(keys)
    vals, found, src = db.get(keys)
    assert bool(jnp.all(found))
    np.testing.assert_allclose(np.asarray(vals[:, 0]), keys.astype(np.float32))


def test_get_missing_returns_not_found():
    db = mkdb()
    db.put(np.arange(10, dtype=np.int32))
    _, found, src = db.get(np.asarray([999, 1000], np.int32))
    assert not bool(jnp.any(found))
    assert all(int(s) == -1 for s in src)


def test_update_in_place_supersedes():
    db = mkdb()
    keys = np.asarray([3, 4], np.int32)
    db.put(keys)
    db.put(keys, vals=jnp.full((2, 2), 99.0))
    vals, found, _ = db.get(keys)
    assert bool(jnp.all(found))
    np.testing.assert_allclose(np.asarray(vals), 99.0)


def test_delete_with_tombstone_hides_slow_copy():
    db = mkdb()
    keys = np.arange(600, dtype=np.int32)       # overflow fast tier
    for i in range(0, 600, 100):
        db.put(keys[i:i + 100])
    assert db.counters["compactions"] > 0       # some keys now on slow tier
    victim = np.asarray([0, 1, 2], np.int32)
    db.delete(victim)
    _, found, _ = db.get(victim)
    assert not bool(jnp.any(found))


def test_scan_merges_tiers_sorted():
    db = mkdb()
    keys = np.arange(0, 600, dtype=np.int32)
    rng = np.random.default_rng(0)
    perm = rng.permutation(keys)
    for i in range(0, 600, 100):
        db.put(perm[i:i + 100])
    got, ok = db.scan(100, 50)
    got = np.asarray(got)[np.asarray(ok)]
    np.testing.assert_array_equal(got, np.arange(100, 100 + len(got)))
    assert len(got) == 50


def test_compaction_conserves_keys_and_run_invariants():
    db = mkdb()
    rng = np.random.default_rng(1)
    written = set()
    for i in range(25):
        ks = rng.integers(0, CFG.key_space, size=100).astype(np.int32)
        db.put(ks)
        written |= set(ks.tolist())
    s = db.state
    fast = set(np.asarray(s.fast_keys[s.fast_keys >= 0]).tolist())
    slow = set(np.asarray(s.slow_keys[s.slow_keys >= 0]).tolist())
    assert written == (fast | slow), "keys lost or invented"
    assert not (fast & slow) or True  # overlap allowed: stale slow copies
    # runs: active, disjoint, keys in range
    act = np.asarray(s.run_active)
    lo, hi = np.asarray(s.run_lo), np.asarray(s.run_hi)
    iv = sorted((lo[i], hi[i]) for i in np.nonzero(act)[0])
    for (l1, h1), (l2, h2) in zip(iv, iv[1:]):
        assert h1 <= l2
    runs = np.asarray(s.slow_run)
    sk = np.asarray(s.slow_keys)
    live = sk >= 0
    assert np.all(act[runs[live]]), "slow object in dead run"
    assert np.all((lo[runs[live]] <= sk[live]) & (sk[live] < hi[runs[live]]))


def test_fast_values_supersede_slow_after_update():
    db = mkdb()
    keys = np.arange(500, dtype=np.int32)
    for i in range(0, 500, 100):
        db.put(keys[i:i + 100])
    # update everything (now some live on slow): new values must win
    db.put(keys[:100], vals=jnp.full((100, 2), -5.0))
    vals, found, _ = db.get(keys[:100])
    assert bool(jnp.all(found))
    np.testing.assert_allclose(np.asarray(vals), -5.0)


def test_rate_limiting_never_drops_writes():
    db = mkdb()
    rng = np.random.default_rng(2)
    for _ in range(20):
        ks = rng.integers(0, CFG.key_space, size=120).astype(np.int32)
        db.put(ks)
        _, found, _ = db.get(ks)
        assert bool(jnp.all(found))


@pytest.mark.parametrize("n_tiers", [2, 3])
def test_sequential_load_keeps_every_record(n_tiers):
    """YCSB's load phase (sequential puts of every key) into a bottom
    tier sized to the key space: every record must read back with its
    value.  Merges, at the slab boundary and at deep boundaries alike,
    used to write their remainder as a small run of its own; those runs
    piled up until the run directory was full, and later merges lost
    rows."""
    from repro import workloads as W
    n = 4096
    tiers = (n // 8, n) if n_tiers == 2 else (n // 8, n // 2, n)
    cfg = CFG._replace(key_space=n, fast_slots=n // 8, slow_slots=n,
                       tracker_slots=n // 10, pin_threshold=0.7,
                       tier_slots=tiers)
    db = PrismDB(cfg, seed=0)
    db.reset_workload(seed=0)
    db.run_workload(W.spec(read=0.0, wdist="seq"), n // 256, 256)
    keys = np.arange(n, dtype=np.int32)
    vals, found, _ = db.get(keys)
    assert db.counters["compactions"] > 0
    assert db.counters["comp_by_boundary"][-1] > 0
    assert int((~np.asarray(found)).sum()) == 0
    np.testing.assert_array_equal(np.asarray(vals[:, 0]),
                                  keys.astype(np.float32))


def _oracle_random_ops(ops):
    """Random op sequence vs a python-dict oracle."""
    cfg = TierConfig(key_space=512, fast_slots=64, slow_slots=1024,
                     value_width=1, max_runs=32, run_size=32,
                     bloom_bits_per_run=1 << 10, tracker_slots=256,
                     n_buckets=16, pin_threshold=0.1)
    db = PrismDB(cfg, seed=3)
    oracle = {}
    ctr = 0.0
    for op, key in ops:
        karr = np.asarray([key], np.int32)
        if op == "put":
            ctr += 1.0
            db.put(karr, vals=jnp.full((1, 1), ctr))
            oracle[key] = ctr
        elif op == "del":
            db.delete(karr)
            oracle.pop(key, None)
        else:
            vals, found, _ = db.get(karr)
            if key in oracle:
                assert bool(found[0]), f"missing key {key}"
                assert float(vals[0, 0]) == oracle[key]
            else:
                assert not bool(found[0]), f"phantom key {key}"


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.lists(st.tuples(st.sampled_from(["put", "get", "del"]),
                              st.integers(0, 400)),
                    min_size=5, max_size=60))
    def test_oracle_random_ops(ops):
        _oracle_random_ops(ops)
else:
    def test_oracle_random_ops():
        """Deterministic fallback when hypothesis is absent."""
        rng = np.random.default_rng(11)
        ops = [(("put", "get", "del")[rng.integers(0, 3)],
                int(rng.integers(0, 400))) for _ in range(60)]
        _oracle_random_ops(ops)


def test_bloom_no_false_negatives():
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.choice(10000, 500, replace=False), jnp.int32)
    filters = bloom.init(4, 1 << 12)
    filters = bloom.set_run(filters, jnp.int32(1), keys,
                            jnp.ones(500, bool))
    hit = bloom.query(filters, jnp.asarray([1]), keys)
    assert bool(jnp.all(hit)), "bloom false negative"


def test_bloom_fp_rate_reasonable():
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.choice(100000, 1000, replace=False), jnp.int32)
    other = jnp.asarray(rng.choice(100000, 1000, replace=False) + 100000,
                        jnp.int32)
    filters = bloom.init(2, 1 << 14)          # ~16 bits/key
    filters = bloom.set_run(filters, jnp.int32(0), keys,
                            jnp.ones(1000, bool))
    fp = float(jnp.mean(bloom.query(filters, jnp.asarray([0]), other)))
    assert fp < 0.05, fp


def test_full_run_directory_keeps_slow_index_consistent():
    """Merges that find the run directory full write rows that belong to
    no run.  A later merge over their key range must free them with the
    rest of its window: the slow index stays the sorted index of the
    slow pool, and no key put leaves the pools.  (Such rows are not
    behind any run's Bloom filter, so a get does not see them: a full
    run directory is a capacity limit, not a state to run in.)"""
    n = 2048
    cfg = CFG._replace(key_space=n, fast_slots=256, slow_slots=n,
                       max_runs=4, run_size=128, tracker_slots=n // 10,
                       n_buckets=32)
    db = PrismDB(cfg, seed=0)
    rng = np.random.default_rng(0)
    for _ in range(3):
        keys = rng.permutation(n // 2).astype(np.int32)
        for i in range(0, len(keys), 128):
            db.put(keys[i:i + 128])
    st = db.state
    slow = np.asarray(st.slow_keys)
    assert (np.asarray(st.slow_run)[slow >= 0] == cfg.max_runs).any()
    idx = np.asarray(st.sidx_keys)
    np.testing.assert_array_equal(idx[idx != np.iinfo(np.int32).max],
                                  np.sort(slow[slow >= 0]))
    fast = np.asarray(st.fast_keys)
    assert set(keys.tolist()) == set(slow[slow >= 0].tolist()) | \
        set(fast[fast >= 0].tolist())
