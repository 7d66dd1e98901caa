"""Device-resident workload engine: sampler distributions vs analytic
references, phase schedules, trace replay, multi-tenant vmapping, the
YCSB-E scan path, and seed reproducibility."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import workloads as W
from repro.core import PrismDB, TierConfig, engine
from repro.core.db import PartitionedDB
from repro.workloads import reference as R
from repro.workloads import sampler
from repro.workloads.spec import HASHED, LATEST, UNIFORM, ZIPF

CFG = TierConfig(key_space=1 << 12, fast_slots=256, slow_slots=1 << 12,
                 value_width=2, max_runs=64, run_size=128,
                 bloom_bits_per_run=1 << 12, tracker_slots=1 << 10,
                 n_buckets=32, pin_threshold=0.1)

KS = 1 << 10
M = 200_000


def _tv(p, q):
    return 0.5 * np.abs(np.asarray(p) - np.asarray(q)).sum()


def _freqs(keys, n):
    return np.bincount(np.asarray(keys), minlength=n) / len(keys)


# ------------------------------------------------------- sampler vs analytic

def test_device_zipf_ranks_match_analytic_pmf():
    u = jax.random.uniform(jax.random.PRNGKey(0), (M,))
    ranks = sampler.zipf_ranks(u, KS, jnp.float32(0.99))
    # TV ~0.02 is the sampling-noise floor at M=200k over 1024 bins
    assert _tv(_freqs(ranks, KS), R.zipf_rank_pmf(KS, 0.99)) < 0.03


def test_device_and_host_reference_agree():
    """Same uniforms -> same ranks (up to 1-ulp pow differences between
    XLA and numpy flooring a handful of ranks by one), so the corrected
    host reference can referee distribution tests."""
    u = np.random.default_rng(0).random(4096, dtype=np.float32)
    for theta in (0.6, 0.99, 1.2):
        dev = np.asarray(sampler.zipf_ranks(jnp.asarray(u), KS,
                                            jnp.float32(theta)))
        host = R.ranks_from_uniforms_host(u, KS, theta)
        assert np.abs(dev - host).max() <= 1
        assert (dev != host).mean() < 0.01
    # and the scramble matches under uint32 wraparound
    ranks = np.arange(KS, dtype=np.int32)
    dev = sampler.scramble(jnp.asarray(ranks), jnp.int32(37), KS)
    np.testing.assert_array_equal(np.asarray(dev),
                                  R.scramble_host(ranks, 37, KS))


def test_bounded_zipf_has_no_modulo_alias_bias():
    """The old host sampler folded numpy.zipf's unbounded tail onto the
    key space with a modulo, inflating key frequencies beyond the true
    (truncated) distribution.  The bounded sampler's key histogram must
    match the analytic pmf pushed through the scramble, and the aliasing
    bias must be demonstrably present in the OLD formula."""
    rng = np.random.default_rng(1)
    keys = R.zipf_keys_host(rng, 1.2, M, KS)
    pmf = R.zipf_key_pmf(KS, 1.2)
    assert _tv(_freqs(keys, KS), pmf) < 0.03
    hot = int(np.argmax(pmf))
    f_hot = (keys == hot).mean()
    assert abs(f_hot - pmf[hot]) < 0.05 * pmf[hot] + 3e-3
    # the regression the fix removes: modulo-folding vs correct
    # truncation (rejection) of the SAME unbounded sampler -- the folded
    # tail measurably inflates the cold half of the rank space
    raw = rng.zipf(1.2, 4 * M)
    aliased = (raw[:M] - 1) % KS
    rejected = raw[raw <= KS][:M] - 1
    cold_aliased = (aliased >= KS // 2).mean()
    cold_rejected = (rejected >= KS // 2).mean()
    assert cold_aliased > 1.1 * cold_rejected


def test_uniform_sampler_is_flat():
    keys, _ = sampler.sample_keys(jax.random.PRNGKey(2), jnp.int32(UNIFORM),
                                  jnp.float32(0.0), jnp.int32(0),
                                  jnp.int32(0), M, 256)
    f = _freqs(keys, 256)
    assert f.max() / f.mean() < 1.2 and f.min() > 0


def test_latest_sampler_concentrates_behind_insert_pointer():
    ptr = 500
    keys, _ = sampler.sample_keys(jax.random.PRNGKey(3), jnp.int32(LATEST),
                                  jnp.float32(1.5), jnp.int32(0),
                                  jnp.int32(ptr), M, KS)
    dist = np.mod(ptr - 1 - np.asarray(keys), KS)
    # analytic CDF at rank 31 for theta=1.5 is ~0.85
    assert (dist < 32).mean() > 0.75
    assert dist.mean() < KS / 8


def test_hashed_inserts_permute_the_key_space():
    """A scrambled load order: key_space inserts from the pointer load
    every key exactly once, out of order."""
    ks = 1 << 12
    keys, ptr = sampler.sample_keys(jax.random.PRNGKey(5), jnp.int32(HASHED),
                                    jnp.float32(0.0), jnp.int32(0),
                                    jnp.int32(ks // 2), ks, ks)
    keys = np.asarray(keys)
    assert int(ptr) == ks // 2 + ks
    assert np.array_equal(np.sort(keys), np.arange(ks))
    assert np.abs(np.diff(keys)).mean() > ks / 8     # not sequential


def test_hot_offset_moves_the_hot_set():
    u = jax.random.uniform(jax.random.PRNGKey(4), (64_000,))
    ranks = sampler.zipf_ranks(u, KS, jnp.float32(1.2))
    a = sampler.scramble(ranks, jnp.int32(0), KS)
    b = sampler.scramble(ranks, jnp.int32(KS // 3), KS)
    hot_a = set(np.argsort(_freqs(a, KS))[-10:].tolist())
    hot_b = set(np.argsort(_freqs(b, KS))[-10:].tolist())
    assert len(hot_a & hot_b) <= 2       # hot sets essentially disjoint


# ------------------------------------------------------------- schedules

def test_phase_schedule_boundaries_are_exact():
    sched = W.schedule([(W.spec(read=0.0), 3),          # all puts
                        (W.spec(read=1.0), 4),          # all gets
                        (W.spec(read=0.0, scan=1.0, put=0.0), 2)])
    assert W.total_batches(sched) == 9
    ops, _ = W.sample_ops(jax.random.PRNGKey(0), sched, 9, 8,
                          key_space=KS, value_width=1)
    np.testing.assert_array_equal(
        np.asarray(ops.kind),
        [engine.PUT] * 3 + [engine.GET] * 4 + [engine.SCAN] * 2)
    # t0 continues the same timeline: steps 3..6 are the GET phase
    ops2, _ = W.sample_ops(jax.random.PRNGKey(0), sched, 4, 8,
                           key_space=KS, value_width=1, t0=3)
    np.testing.assert_array_equal(np.asarray(ops2.kind), [engine.GET] * 4)


def test_schedule_stacks_and_indexes_specs():
    sched = W.schedule([(W.ycsb("A"), 5), (W.ycsb("C"), 5)])
    assert float(W.spec_at(sched, jnp.int32(0)).p_get) == 0.5
    assert float(W.spec_at(sched, jnp.int32(7)).p_get) == 1.0
    assert float(W.spec_at(sched, jnp.int32(99)).p_get) == 1.0  # clamps


# ----------------------------------------------------------- trace replay

def test_trace_pack_unpack_roundtrip():
    trace = [("put", np.arange(40, dtype=np.int32)),
             ("get", np.array([3, 7, 9], np.int32)),
             ("scan", np.array([0, 20], np.int32),
              np.array([5, 9], np.int32)),
             ("delete", np.array([7], np.int32))]
    ops = W.pack_trace(trace, batch=64, value_width=2)
    assert ops.keys.shape == (4, 64)
    back = W.unpack_trace(ops)
    assert [r[0] for r in back] == [r[0] for r in trace]
    for orig, got in zip(trace, back):
        np.testing.assert_array_equal(orig[1], got[1])
        if orig[0] == "scan":
            np.testing.assert_array_equal(orig[2], got[2])


def test_trace_replay_executes_in_one_dispatch():
    trace = [("put", np.arange(64, dtype=np.int32)),
             ("get", np.arange(0, 64, 2, dtype=np.int32)),
             ("scan", np.array([10], np.int32), np.array([8], np.int32))]
    db = PrismDB(CFG, seed=0)
    res = db.run_ops(W.pack_trace(trace, batch=64,
                                  value_width=CFG.value_width))
    assert db.dispatches == 1
    assert np.asarray(res.found[1])[:32].all()      # all gets hit
    assert int(res.src[2][0]) == 8                  # scan returned 8 keys


def test_trace_rejects_oversized_records():
    import pytest
    with pytest.raises(ValueError):
        W.pack_trace([("put", np.arange(65))], batch=64, value_width=1)


# ------------------------------------------------------------ YCSB-E scans

def test_scan_op_counts_match_oracle():
    db = PrismDB(CFG, seed=1)
    inserted = np.arange(0, 900, 3, dtype=np.int32)        # 300 keys
    for i in range(0, 300, 100):
        db.put(inserted[i:i + 100])                        # demotes to slow
    db.delete(inserted[:10])                               # 0,3,..,27 gone
    live = np.sort(np.asarray(sorted(set(inserted[10:].tolist()))))
    starts = np.array([0, 30, 300, 880], np.int32)
    lens = np.array([8, 5, 10, 20], np.int32)
    got = np.asarray(db.scan_ops(starts, lens))
    for s, ln, g in zip(starts, lens, got):
        expect = min(int(ln), int((live >= s).sum()))
        assert g == expect, (s, ln, g, expect)
    c = db.counters
    assert c["scans"] == 4
    assert c["scan_reads"] <= c["slow_reads"]


def test_ycsb_e_spec_emits_real_scans():
    db = PrismDB(CFG, seed=2)
    db.put(np.arange(256, dtype=np.int32))
    db.reset_workload(seed=0)
    stats = db.run_workload(W.ycsb("E"), 16, 32)
    kinds = np.asarray(stats.kind)
    assert (kinds == engine.SCAN).sum() >= 10       # ~95% scan batches
    assert (kinds == engine.PUT).sum() >= 0
    assert int(np.asarray(stats.returned).sum()) > 0
    assert db.counters["scan_reads"] + db.counters["fast_reads"] > 0


# ------------------------------------------------------- fused execution

def test_workload_segment_is_one_dispatch():
    db = PrismDB(CFG, seed=0)
    db.reset_workload(seed=0)
    db.run_workload(W.ycsb("A"), 12, 64)
    assert db.dispatches == 1
    # a NEW schedule needs a timeline reset or its early phases are
    # skipped (the step clock carries across segments by design, so a
    # warmup/measure split stays on one timeline)
    db.reset_workload(seed=0)
    sched = W.scenario("delete-churn", CFG.key_space, 12)
    stats = db.run_workload(sched, W.total_batches(sched), 64)
    assert db.dispatches == 2
    kinds = np.asarray(stats.kind)
    assert (kinds == engine.DELETE).sum() > 0    # shrink phases really ran
    assert (kinds == engine.PUT).sum() > 0       # grow phases really ran


def test_seed_reproducibility_and_divergence():
    def go(seed):
        db = PrismDB(CFG, seed=0)
        db.reset_workload(seed=seed)
        st = db.run_workload(W.ycsb("A"), 10, 64)
        return np.asarray(st.kind), db.counters

    k1, c1 = go(5)
    k2, c2 = go(5)
    k3, c3 = go(6)
    np.testing.assert_array_equal(k1, k2)
    assert c1 == c2                                  # bit-reproducible
    assert (k1 != k3).any() or c1 != c3              # seed actually matters


# ----------------------------------------------------------- multi-tenant

def test_multitenant_vmapped_streams():
    cfg = CFG._replace(value_width=1)
    pdb = PartitionedDB(cfg, n_partitions=4, seed=0)
    works = [W.ycsb("A"), W.ycsb("C"), W.twitter("cluster39"),
             W.spec(read=0.0, dist="uniform")]
    pdb.reset_workload(seed=0)
    stats = pdb.run_workload(works, 6, 32)
    assert pdb.dispatches == 1
    assert np.asarray(stats.kind).shape == (4, 6)
    assert np.asarray(stats.found).shape == (4, 6)
    # tenant 1 is read-only, tenant 3 write-only
    assert (np.asarray(stats.kind)[1] == engine.GET).all()
    assert (np.asarray(stats.kind)[3] == engine.PUT).all()
    # per-partition counters report independent activity
    ctr = pdb.counters
    assert ctr["puts"][3] == 6 * 32
    assert ctr["gets"][1] == 6 * 32


def test_multitenant_shared_schedule_diverges_per_tenant():
    cfg = CFG._replace(value_width=1)
    pdb = PartitionedDB(cfg, n_partitions=2, seed=0)
    pdb.reset_workload(seed=0)
    stats = pdb.run_workload(W.ycsb("A"), 12, 32)
    kinds = np.asarray(stats.kind)
    assert kinds.shape == (2, 12)
    assert (kinds[0] != kinds[1]).any()     # split rngs, distinct streams
