"""Device-resident engine step: fused control plane, policy machine,
routing drops, append-only fill accounting, scan reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PrismDB, TierConfig, compaction, engine, policy,
                        tiers)
from repro.core.db import PartitionedDB, route_batch

CFG = TierConfig(key_space=1 << 13, fast_slots=256, slow_slots=1 << 12,
                 value_width=2, max_runs=64, run_size=128,
                 bloom_bits_per_run=1 << 12, tracker_slots=1 << 10,
                 n_buckets=32, pin_threshold=0.1)


# ------------------------------------------------------------- fused step

def test_single_dispatch_per_client_batch():
    """Steady state: one jitted engine call per put/get/delete batch -- no
    host-driven compaction loop (acceptance criterion)."""
    db = PrismDB(CFG, seed=0)
    keys = np.arange(600, dtype=np.int32)
    for i in range(0, 600, 100):                # overflows fast tier
        db.put(keys[i:i + 100])
    assert db.counters["compactions"] > 0       # compactions DID run...
    assert db.dispatches == 6                   # ...inside the 6 dispatches
    db.get(keys[:100])
    db.delete(keys[:4])
    assert db.dispatches == 8


def test_run_ops_scan_matches_per_batch_stepping():
    """A lax.scan-driven op stream must land in exactly the state that
    per-batch dispatches produce (same rng path, same ops)."""
    k1 = np.arange(64, dtype=np.int32)
    k2 = np.arange(64, 192, 2, dtype=np.int32)

    db_a = PrismDB(CFG, seed=7)
    db_a.put(k1)
    db_a.put(k2)
    vals_a, found_a, _ = db_a.get(k1)

    db_b = PrismDB(CFG, seed=7)
    mk = lambda kind, keys: engine.make_op(kind, keys,
                                           value_width=CFG.value_width)
    ops = jax.tree.map(lambda *xs: jnp.stack(xs),
                       mk(engine.PUT, k1), mk(engine.PUT, k2),
                       mk(engine.GET, k1))
    res = db_b.run_ops(ops)
    assert db_b.dispatches == 1
    np.testing.assert_array_equal(np.asarray(found_a),
                                  np.asarray(res.found[2]))
    np.testing.assert_allclose(np.asarray(vals_a), np.asarray(res.vals[2]))
    np.testing.assert_array_equal(np.asarray(db_a.state.fast_keys),
                                  np.asarray(db_b.state.fast_keys))
    for a, b in zip(db_a.state.ctr, db_b.state.ctr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rate_limit_inside_jit_never_drops_writes():
    db = PrismDB(CFG, seed=2)
    rng = np.random.default_rng(2)
    for _ in range(20):
        ks = rng.integers(0, CFG.key_space, size=120).astype(np.int32)
        db.put(ks)
        _, found, _ = db.get(ks)
        assert bool(jnp.all(found))


# --------------------------------------------------------- policy machine

def test_policy_transitions_under_jitted_step():
    """§5.3 DETECT -> ACTIVE -> (monitor at epoch end) -> COOLDOWN ->
    DETECT, driven end-to-end through the fused engine step."""
    pol = policy.PolicyConfig(epoch_ops=64, cooldown_ops=128,
                              min_improvement=2.0,      # epoch never improves
                              read_heavy_frac=0.5, slow_tracked_frac=0.2)
    db = PrismDB(CFG, seed=0, pol_cfg=pol)
    rng = np.random.default_rng(0)
    keys = np.arange(900, dtype=np.int32)
    for i in range(0, 900, 100):                # push most keys to slow
        db.put(keys[i:i + 100])
    phases = [int(db.pol.phase)]
    for _ in range(40):
        db.get(rng.integers(0, 900, 64).astype(np.int32))
        phases.append(int(db.pol.phase))
    assert policy.ACTIVE in phases, phases
    assert policy.COOLDOWN in phases, phases
    # ACTIVE is entered before its COOLDOWN, and DETECT follows a COOLDOWN
    first_active = phases.index(policy.ACTIVE)
    first_cool = phases.index(policy.COOLDOWN)
    assert first_active < first_cool
    assert policy.DETECT in phases[first_cool:], phases
    # ACTIVE epochs ran their compaction budget inside the same dispatches
    assert db.counters["compactions"] > 0


def test_policy_counts_scans_as_reads():
    """A scan-only workload is read traffic: the DETECT window must count
    scans in the read fraction (the engine advances the policy on scan
    batches), so §5.3 triggers without a single get."""
    pol = policy.PolicyConfig(epoch_ops=64, cooldown_ops=10**6,
                              min_improvement=-1.0,     # epochs continue
                              read_heavy_frac=0.5, slow_tracked_frac=0.2)
    db = PrismDB(CFG, seed=0, pol_cfg=pol)
    keys = np.arange(900, dtype=np.int32)
    for i in range(0, 900, 100):                # push most keys to slow
        db.put(keys[i:i + 100])
    before = db.counters["compactions"]
    phases = []
    for _ in range(6):
        db.scan_ops(np.arange(0, 640, 10, dtype=np.int32),
                    np.full(64, 4, np.int32))
        phases.append(int(db.pol.phase))
    assert policy.ACTIVE in phases, phases
    assert db.counters["compactions"] > before


def test_policy_cooldown_blocks_read_compactions():
    pol = policy.PolicyConfig(epoch_ops=32, cooldown_ops=10**6,
                              min_improvement=2.0,
                              read_heavy_frac=0.5, slow_tracked_frac=0.2)
    db = PrismDB(CFG, seed=0, pol_cfg=pol)
    rng = np.random.default_rng(1)
    keys = np.arange(900, dtype=np.int32)
    for i in range(0, 900, 100):
        db.put(keys[i:i + 100])
    for _ in range(20):
        db.get(rng.integers(0, 900, 64).astype(np.int32))
        if int(db.pol.phase) == policy.COOLDOWN:
            break
    assert int(db.pol.phase) == policy.COOLDOWN
    before = db.counters["compactions"]
    for _ in range(5):                           # far below cooldown_ops
        db.get(rng.integers(0, 900, 64).astype(np.int32))
    assert int(db.pol.phase) == policy.COOLDOWN
    assert db.counters["compactions"] == before


# ------------------------------------------------------------ partitions

def test_route_batch_counts_overflow():
    keys = jnp.asarray(np.arange(64), jnp.int32)
    routed, valid, dropped = route_batch(keys, 4, 8)
    # dropped is per-DESTINATION-partition; totals still conserve ops
    assert dropped.shape == (4,)
    assert int(valid.sum()) + int(dropped.sum()) == 64
    # routed keys are a subset of the input, no invented keys
    got = np.asarray(routed)[np.asarray(valid)]
    assert set(got.tolist()) <= set(range(64))


def test_partitioned_db_surfaces_drops():
    cfg = TierConfig(key_space=1 << 12, fast_slots=256, slow_slots=1 << 12,
                     value_width=1, max_runs=32, run_size=128,
                     bloom_bits_per_run=1 << 11, tracker_slots=512,
                     n_buckets=16, pin_threshold=0.1)
    pdb = PartitionedDB(cfg, n_partitions=4, seed=0)
    # all-identical keys hash to ONE partition: batch 64, pad 2*64/4 = 32
    pdb.put(np.full(64, 5, np.int32))
    assert pdb.dropped == 32                    # counted, not silent
    # balanced batches do not drop
    pdb.put(np.arange(64, dtype=np.int32))
    assert pdb.dropped == 32


def test_partitioned_shares_engine_core():
    """Partitioned put/get round-trips through the same vmapped
    engine_step; single-partition equals PrismDB semantics."""
    cfg = TierConfig(key_space=1 << 12, fast_slots=256, slow_slots=1 << 12,
                     value_width=1, max_runs=32, run_size=128,
                     bloom_bits_per_run=1 << 11, tracker_slots=512,
                     n_buckets=16, pin_threshold=0.1)
    pdb = PartitionedDB(cfg, n_partitions=4, seed=0)
    keys = np.arange(128, dtype=np.int32)
    pdb.put(keys)
    vals, found, src = pdb.get(keys)
    routed, valid, _ = route_batch(jnp.asarray(keys, jnp.int32), 4, 64)
    got = set(np.asarray(routed)[np.asarray(valid)
                                 & np.asarray(found)].tolist())
    assert got == set(range(128))
    assert pdb.dispatches == 2


# ------------------------------------------------- append-only accounting

def _filled_append_only():
    db = PrismDB(CFG, seed=0, append_only=True)
    keys = np.arange(600, dtype=np.int32)
    for i in range(0, 600, 100):
        db.put(keys[i:i + 100])                 # demotes a lot to slow
    db.put(keys)                                # update ALL -> stale copies
    return db


def test_append_only_virtual_fill_grows_on_updates():
    db = _filled_append_only()
    assert int(db.estate.virtual_extra) > 0
    _, found, _ = db.get(np.arange(600, dtype=np.int32))
    assert bool(jnp.all(found))                 # rate limit never drops


def test_append_only_decay_equals_actual_merged_count():
    """virtual_extra must decay by the compaction's measured superseded
    count -- zero merges, zero decay (satellite fix: no more key-range
    fraction drift)."""
    db = _filled_append_only()
    est, ecfg = db.estate, db.ecfg
    ve = int(est.virtual_extra)
    assert ve > 0
    # per-round exact accounting: replay the rng split _compact1 will use
    # to predict each round's stats, and check the fill moves by EXACTLY
    # the measured superseded count (zero merges -> zero decay)
    decayed = False
    for _ in range(10):
        _, sub = jax.random.split(est.rng)
        _, stats = compaction.compact_once(
            est.tier, CFG, rng=sub, promote=ecfg.promote,
            precise=ecfg.precise, selection=ecfg.selection,
            pin_mode=ecfg.pin_mode)
        est = engine._compact1(est, ecfg, None, None)
        expect = max(ve - int(stats.n_superseded), 0)
        assert int(est.virtual_extra) == expect
        decayed |= int(stats.n_superseded) > 0
        ve = expect
    if decayed:                     # merges happened -> fill really shrank
        assert ve < int(db.estate.virtual_extra)


# ------------------------------------------------------------------ scan

def test_scan_matches_bruteforce_reference_with_tombstones():
    db = PrismDB(CFG, seed=1)
    rng = np.random.default_rng(3)
    oracle = set()
    for _ in range(6):
        ks = rng.choice(2000, 100, replace=False).astype(np.int32)
        db.put(ks)
        oracle |= set(ks.tolist())
    # delete keys across tiers: some live on slow -> fast-tier tombstones
    victims = np.asarray(sorted(oracle))[::7][:30].astype(np.int32)
    db.delete(victims)
    oracle -= set(victims.tolist())
    tomb = np.asarray(db.state.fast_ver) < 0
    assert tomb.any(), "no tombstones created; test setup broken"
    for lo in (0, 137, 800, 1500):
        got, ok = db.scan(lo, 40)
        got = np.asarray(got)[np.asarray(ok)]
        ref = np.asarray(sorted(k for k in oracle if k >= lo))[:40]
        # scan returns "up to n": must be an exact prefix of the oracle's
        # sorted live keys (order, membership, tombstone suppression), and
        # the windowed over-fetch must not starve it badly
        np.testing.assert_array_equal(got, ref[:len(got)])
        assert len(got) >= min(len(ref), 20), \
            f"scan({lo}) returned {len(got)} of {len(ref)} live keys"
        assert not (set(got.tolist()) & set(victims.tolist()))


# three tiers: a small middle tier, so deep merges run too
CFG3 = TierConfig(key_space=1 << 11, fast_slots=128, slow_slots=1 << 10,
                  value_width=2, max_runs=32, run_size=64,
                  bloom_bits_per_run=1 << 12, tracker_slots=1 << 9,
                  n_buckets=32, pin_threshold=0.1,
                  tier_slots=(128, 256, 1 << 10))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("cfg,quantum", [(CFG, 0), (CFG, 3), (CFG3, 0),
                                         (CFG3, 3)],
                         ids=["N2-q0", "N2-q3", "N3-q0", "N3-q3"])
def test_kind_gated_scan_lane_is_bit_identical(cfg, quantum, backend):
    """Through the kind-gated ``engine_step`` over a seeded stream of
    put/get/delete/scan batches: a scan step's results and whole tier
    state (every counter included) are ``tiers.scan_batch``'s on the
    state its lane reads (the same step with every lane invalid), and
    any other step leaves the scan counters unchanged."""
    db = PrismDB(cfg, seed=0, backend=backend, compaction_quantum=quantum)
    chunk = db.ecfg.scan_chunk
    step = engine.jit_step(db.ecfg, donate=False)
    lanes = jax.jit(lambda t, k, n, v: tiers.scan_batch(
        t, cfg, k, n, v, chunk=chunk))
    state = db.estate
    rng = np.random.default_rng(7)
    batch, n_keys = 64, 600
    kinds = [engine.PUT] * 8 + list(rng.choice(
        [engine.PUT, engine.GET, engine.DELETE, engine.SCAN], 40,
        p=[0.3, 0.25, 0.15, 0.3]))
    for t, kind in enumerate(kinds):
        keys = rng.choice(n_keys, batch, replace=False).astype(np.int32)
        valid = rng.random(batch) < (1.0 if t < 8 else 0.8)
        aux = rng.integers(-4, chunk + 8, batch).astype(np.int32)
        vals = np.stack([keys, np.full(batch, t)], 1).astype(np.float32)
        op = engine.make_op(int(kind), jnp.asarray(keys), jnp.asarray(vals),
                            valid=jnp.asarray(valid), aux=jnp.asarray(aux),
                            value_width=cfg.value_width)
        new, res = step(state, op)
        src, found = np.asarray(res.src), np.asarray(res.found)
        if kind == engine.SCAN:
            before, _ = step(state, op._replace(
                valid=jnp.zeros(batch, bool)))
            want, n_live = lanes(before.tier, op.keys,
                                 jnp.minimum(op.aux, chunk), op.valid)
            assert all(map(np.array_equal,
                           *map(jax.tree.leaves, jax.device_get(
                               (new.tier, want)))))
            np.testing.assert_array_equal(src, np.asarray(n_live))
            np.testing.assert_array_equal(found, np.asarray(n_live) > 0)
        else:
            for name in ("scans", "scan_objs", "scan_reads"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(new.tier.ctr, name)),
                    np.asarray(getattr(state.tier.ctr, name)), name)
            if kind != engine.GET:
                assert (src == -1).all() and not found.any()
        if kind != engine.GET:
            assert not np.asarray(res.vals).any()
        state = new
    ctr = tiers.counters_dict(state.tier.ctr)
    assert ctr["scan_objs"] > 0 and ctr["gets"] > 0
    assert ctr["compactions"] > 0


def test_scan_excludes_every_deleted_key():
    db = PrismDB(CFG, seed=1)
    for i in range(0, 400, 100):                # forces demotions
        db.put(np.arange(i, i + 100, dtype=np.int32))
    db.delete(np.arange(100, 140, dtype=np.int32))
    got, ok = db.scan(90, 20)
    got = np.asarray(got)[np.asarray(ok)]
    assert len(got) > 0
    assert not (set(got.tolist()) & set(range(100, 140)))
    ref = np.asarray([*range(90, 100), *range(140, 400)])
    np.testing.assert_array_equal(got, ref[:len(got)])
