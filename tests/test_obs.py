"""Device-resident observability plane: histogram quantiles vs an exact
host-side oracle, conservation invariants through the fused engine, ring
wrap semantics, and the vmapped merge-by-summation path.

The quantile property: the estimator works from the log2 histogram only,
so it cannot recover the exact order statistic -- but it MUST land in the
same bucket as the exact numpy order statistic (rank = ceil(q*N),
1-based), inside that bucket's (lo, hi] bounds.  That is the strongest
property a histogram supports, and it is checked exactly.
"""
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

from repro.core import PrismDB, TierConfig, compaction, tiers
from repro.obs import (ObsConfig, bucket_bounds, bucket_of_us,
                       bucket_of_us_np, events_table, quantile_from_hist,
                       quantiles_from_hist, snapshot, timeline_table)
from repro.obs import state as obs_state
from repro.workloads.reference import zipf_keys_host

CFG = TierConfig(key_space=512, fast_slots=64, slow_slots=1024,
                 value_width=1, max_runs=32, run_size=32,
                 bloom_bits_per_run=1 << 10, tracker_slots=256,
                 n_buckets=16, pin_threshold=0.1)

QS = (0.5, 0.99, 0.999)


# ----------------------------------------------------- bucket function

def test_bucket_np_mirrors_device():
    us = np.concatenate([
        np.asarray([0.0, 0.5, 1.0, 1.0001, 2.0, 2.5, 4.0, 1e9]),
        np.exp2(np.arange(0, 31, dtype=np.float64)),
        np.exp2(np.arange(0, 31, dtype=np.float64)) + 1e-3])
    dev = np.asarray(bucket_of_us(jnp.asarray(us, jnp.float32), 32))
    host = bucket_of_us_np(us, 32)
    np.testing.assert_array_equal(dev, host)


def test_bucket_bounds_partition_the_line():
    lo, hi = bucket_bounds(8)
    assert lo[0] == 0.0 and hi[0] == 1.0
    np.testing.assert_array_equal(lo[1:], hi[:-1])   # contiguous
    # bucket membership agrees with the bounds: us in (lo_b, hi_b]
    for us in (0.3, 1.0, 1.5, 2.0, 3.7, 64.0, 100.0):
        b = int(bucket_of_us_np(us, 8))
        assert lo[b] < us <= hi[b] or b == 7    # top bucket absorbs


# ------------------------------------------- quantiles vs exact oracle

def _check_quantiles(costs: np.ndarray, n_buckets: int = 32):
    """The property: for every q, the estimate lands in the same bucket
    as the exact rank-ceil(q*N) order statistic (within that bucket's
    bounds, which also contain the exact value)."""
    costs = np.asarray(costs, np.float64)
    buckets = bucket_of_us_np(costs, n_buckets)
    hist = np.bincount(buckets, minlength=n_buckets)
    lo, hi = bucket_bounds(n_buckets)
    srt = np.sort(costs)
    n = len(costs)
    for q in QS:
        rank = min(max(int(np.ceil(q * n)), 1), n)
        exact = srt[rank - 1]
        b = int(bucket_of_us_np(exact, n_buckets))
        est = quantile_from_hist(hist, q)
        assert lo[b] <= est <= hi[b], (q, est, exact, b)
        assert est > 0.0


def _random_costs(rng: np.random.Generator):
    kind = rng.integers(0, 3)
    n = int(rng.integers(1, 2000))
    if kind == 0:          # log-uniform across the bucket range
        return np.exp2(rng.uniform(-2, 20, size=n))
    if kind == 1:          # bimodal: fast-hit mode + slow-read mode
        a = rng.normal(8, 2, size=n).clip(0.1)
        b = rng.normal(400, 60, size=n).clip(0.1)
        pick = rng.random(n) < 0.9
        return np.where(pick, a, b)
    return rng.uniform(0.01, 5000, size=n)     # uniform heavy tail


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_quantile_matches_oracle(seed):
        _check_quantiles(_random_costs(np.random.default_rng(seed)))
else:
    def test_quantile_matches_oracle():
        for seed in range(30):
            _check_quantiles(_random_costs(np.random.default_rng(seed)))


def test_quantile_edge_cases():
    assert quantile_from_hist(np.zeros(8, np.int64), 0.99) == 0.0
    one = np.zeros(8, np.int64)
    one[3] = 1                       # single op in (4, 8]
    for q in QS:
        assert 4.0 <= quantile_from_hist(one, q) <= 8.0
    assert quantiles_from_hist(one)["p999"] <= 8.0


# ------------------------------------------- engine-level conservation

def test_engine_hist_mass_and_event_conservation():
    """Histogram mass == valid client ops issued; compaction event count
    (monotonic, wrap-proof) == the engine's compactions counter."""
    db = PrismDB(CFG, seed=0)
    rng = np.random.default_rng(0)
    issued = 0
    for i in range(8):
        keys = rng.integers(0, CFG.key_space, 48).astype(np.int32)
        db.put(keys)
        issued += 48
        db.get(keys)
        issued += 48
        if i % 3 == 2:
            db.delete(keys[:16])
            issued += 16
    snap = db.obs_snapshot()
    assert int(snap["hist"].sum()) == issued
    assert snap["ev_count"] == db.counters["compactions"]
    assert snap["t_pos"] == 8 * 2 + 2         # one row per engine step
    # put/get/delete rows only; the tick row belongs to the serve engine
    assert snap["hist"][obs_state.TICK].sum() == 0
    # percentiles are well-formed on real engine data
    q = quantiles_from_hist(snap["hist"])
    assert 0 < q["p50"] <= q["p99"] <= q["p999"]


def test_timeline_rows_match_counters():
    """The timeline ring's per-step deltas sum to the counter totals
    (while it hasn't wrapped)."""
    db = PrismDB(CFG, seed=0)
    rng = np.random.default_rng(1)
    for _ in range(6):
        db.put(rng.integers(0, CFG.key_space, 32).astype(np.int32))
    snap = db.obs_snapshot()
    rows = timeline_table(snap)
    assert len(rows) == 6
    ctr = db.counters
    for f in ("puts", "slow_writes", "compactions", "fast_writes"):
        assert sum(r[f] for r in rows) == ctr[f], f


# --------------------------------------------------------- ring wrap

def test_event_ring_wraps_monotonically():
    ocfg = ObsConfig(event_len=4)
    obs = obs_state.init(ocfg)
    z = jnp.zeros((), jnp.int32)
    for i in range(7):
        stats = compaction.CompactionStats(
            selected_lo=z, selected_hi=z, score=jnp.float32(i),
            n_demoted=z, n_promoted=z, n_merged=jnp.int32(i),
            n_superseded=z, n_run_read=z, n_run_written=z)
        obs = obs_state.record_compaction(obs, ocfg, step=jnp.int32(i),
                                          trigger=z, stats=stats)
    assert int(obs.ev_count) == 7            # total ever, not ring size
    rows = events_table(snapshot(obs))
    assert len(rows) == 4                    # ring keeps the last 4
    assert [r["step"] for r in rows] == [3, 4, 5, 6]   # oldest first
    assert [r["moved"] for r in rows] == [3, 4, 5, 6]


def test_timeline_ring_wraps():
    ocfg = ObsConfig(timeline_len=4)
    obs = obs_state.init(ocfg)
    for i in range(6):
        delta = tiers.Counters.zeros()._replace(puts=jnp.int32(i))
        obs = obs_state.record_step(obs, ocfg, kind=jnp.int32(0),
                                    n_ops=jnp.int32(8), delta=delta)
    rows = timeline_table(snapshot(obs))
    assert [r["puts"] for r in rows] == [2, 3, 4, 5]
    assert int(obs.hist.sum()) == 6 * 8      # histograms never wrap


# ------------------------------------- vmapped merge-by-summation path

def test_vmapped_states_merge_by_summation():
    """Stacked (vmapped) per-partition ObsStates: one snapshot merges
    histograms/t_pos/ev_count by summation, keeps rings per partition."""
    ocfg = ObsConfig()

    def run(seed):
        obs = obs_state.init(ocfg)
        rng = np.random.default_rng(int(seed))
        for k in range(3):
            delta = tiers.Counters.zeros().update(
                fast_reads=jnp.int32(rng.integers(1, 50)),
                slow_reads=jnp.int32(rng.integers(0, 20)))
            obs = obs_state.record_step(obs, ocfg, kind=jnp.int32(1),
                                        n_ops=jnp.int32(16), delta=delta)
        return obs

    parts = [run(s) for s in range(3)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
    snap = snapshot(stacked)
    assert snap["n_partitions"] == 3
    want = np.sum([np.asarray(p.hist) for p in parts], axis=0)
    np.testing.assert_array_equal(snap["hist"], want)
    assert snap["t_pos"] == 9 and int(snap["hist"].sum()) == 9 * 16
    assert len(timeline_table(snap)) == 9     # per-partition rows kept
    # quantiles over the merged histogram == quantiles of the union
    per_part_mass = [int(np.asarray(p.hist).sum()) for p in parts]
    assert sum(per_part_mass) == int(snap["hist"].sum())


def test_partitioned_db_merged_snapshot():
    from repro.core.db import PartitionedDB
    db = PartitionedDB(CFG, n_partitions=2, seed=0)
    rng = np.random.default_rng(2)
    total = 0
    for _ in range(4):
        db.put(rng.integers(0, CFG.key_space, 64).astype(np.int32))
        total += 64
    snap = db.obs_snapshot()
    # every routed valid lane is in some partition's histogram
    assert int(snap["hist"].sum()) == total - db.dropped
    assert snap["ev_count"] == sum(db.counters["compactions"])


# ------------------------------------------- compaction-burst counters

# three tiers: a small middle tier, so deep (watermark) merges run too
CFG3 = TierConfig(key_space=1 << 11, fast_slots=128, slow_slots=1 << 10,
                  value_width=2, max_runs=32, run_size=64,
                  bloom_bits_per_run=1 << 12, tracker_slots=1 << 9,
                  n_buckets=32, pin_threshold=0.1,
                  tier_slots=(128, 256, 1 << 10))


def test_step_comp_bucket_is_the_bit_length_capped():
    n = np.array([0, 1, 2, 3, 4, 7, 8, 127, 128, 255, 256, 300, 4096])
    want = np.minimum([int(x).bit_length() for x in n],
                      obs_state.STEP_COMP_BUCKETS - 1)
    np.testing.assert_array_equal(
        np.asarray(obs_state.step_comp_bucket(jnp.asarray(n))), want)


@pytest.mark.parametrize("mix", ["A", "C"])
@pytest.mark.parametrize("cfg,quantum", [(CFG, 0), (CFG, 3), (CFG3, 0),
                                         (CFG3, 3)],
                         ids=["N2-q0", "N2-q3", "N3-q0", "N3-q3"])
def test_burst_counters_conserve(mix, cfg, quantum):
    """Through ``PrismDB``: jobs by trigger sum to the jobs and to the
    compactions, steps by compactions to the steps, and every step's
    bucket matches its timeline row's ``compactions``."""
    db = PrismDB(cfg, seed=0, compaction_quantum=quantum)
    rng = np.random.default_rng(11)
    batch = 64
    for keys in rng.permutation(cfg.key_space).astype(np.int32).reshape(
            -1, batch):                         # load every key once
        db.put(keys)
    for t in range(24):                         # YCSB-A or YCSB-C
        keys = zipf_keys_host(rng, 0.99, batch, cfg.key_space).astype(
            np.int32)
        if mix == "A" and t % 2:
            db.put(keys)
        else:
            db.get(keys)
    snap = db.obs_snapshot()
    ctr = db.counters
    assert ctr["compactions"] > 0
    assert int(snap["ev_jobs_t"].sum()) == snap["ev_jobs"] \
        == ctr["compactions"]
    assert ctr["jobs_by_trigger"] == snap["ev_jobs_t"].tolist()
    assert int(snap["step_comp_hist"].sum()) == snap["t_pos"]
    assert ctr["steps_by_compactions"] == snap["step_comp_hist"].tolist()
    rows = timeline_table(snap)
    assert len(rows) == snap["t_pos"] == db.dispatches   # ring not wrapped
    buckets = [min(r["compactions"].bit_length(),
                   obs_state.STEP_COMP_BUCKETS - 1) for r in rows]
    np.testing.assert_array_equal(
        snap["step_comp_hist"],
        np.bincount(buckets, minlength=obs_state.STEP_COMP_BUCKETS))
