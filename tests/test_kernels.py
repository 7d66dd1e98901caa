"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(0)


# ------------------------------------------------------- flash attention

@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,causal,win", [
    (2, 4, 2, 64, 64, 32, True, -1),
    (1, 8, 2, 33, 33, 64, True, -1),
    (2, 2, 2, 17, 80, 16, True, 16),
    (1, 4, 1, 5, 5, 128, False, -1),
    (1, 4, 4, 48, 48, 8, True, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, hq, hkv, sq, sk, d, causal, win, dtype):
    from repro.kernels.flash_attention.ops import mha
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    ref = mha(q, k, v, causal=causal, window=win, backend="reference")
    out = mha(q, k, v, causal=causal, window=win, backend="pallas",
              block_q=32, block_k=32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


# -------------------------------------------------------- paged attention

@pytest.mark.parametrize("b,hq,hkv,d,P,T,K", [
    (2, 4, 2, 32, 16, 8, 4),
    (1, 8, 8, 64, 8, 4, 3),
    (3, 6, 2, 128, 32, 16, 8),
])
def test_paged_attention(b, hq, hkv, d, P, T, K):
    from repro.kernels.paged_attention.ops import decode_attention
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), jnp.float32)
    kp = jnp.asarray(RNG.normal(size=(P, T, hkv, d)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(P, T, hkv, d)), jnp.float32)
    bt = jnp.asarray(RNG.integers(-1, P, size=(b, K)), jnp.int32)
    tm = jnp.asarray(RNG.random((b, K, T)) > 0.2)
    bt = bt.at[:, 0].set(0)
    tm = tm.at[:, 0, 0].set(True)
    ref = decode_attention(q, kp, vp, bt, tm, backend="reference")
    out = decode_attention(q, kp, vp, bt, tm, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------- tier compact

@pytest.mark.parametrize("P,S,W,M", [(16, 32, 128, 12), (8, 64, 256, 30)])
def test_tier_compact_movement(P, S, W, M):
    from repro.core.compaction import Movement
    from repro.kernels.tier_compact.ops import apply_movement_rows
    fp = jnp.asarray(RNG.normal(size=(P, W)), jnp.float32)
    sp = jnp.asarray(RNG.normal(size=(S, W)), jnp.float32)
    # valid promotion destinations must be unique fast slots: at most P
    p_dst = np.concatenate([RNG.permutation(P),
                            np.zeros(max(M - P, 0), np.int64)])[:M]
    p_valid = (RNG.random(M) > 0.5) & (np.arange(M) < P)
    mv = Movement(
        m_src_tier=jnp.asarray(RNG.integers(0, 2, M), jnp.int32),
        m_src_slot=jnp.asarray(RNG.integers(0, P, M), jnp.int32),
        m_dst_slot=jnp.asarray(RNG.permutation(S)[:M], jnp.int32),
        m_valid=jnp.asarray(RNG.random(M) > 0.3),
        p_src_slot=jnp.asarray(RNG.integers(0, S, M), jnp.int32),
        p_dst_slot=jnp.asarray(p_dst, jnp.int32),
        p_valid=jnp.asarray(p_valid))
    r1 = apply_movement_rows(fp, sp, mv, backend="reference")
    r2 = apply_movement_rows(fp, sp, mv, backend="pallas")
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------- clock update

@pytest.mark.parametrize("cap,batch,tile", [
    (1024, 256, 256), (512, 128, 64),
    (1021, 256, None),   # prime capacity > 512: auto tile + table padding
    (331, 64, None),     # prime capacity < 512: whole-table tile
])
def test_clock_update_kernel(cap, batch, tile):
    from repro.core import tracker
    from repro.kernels.clock_update.ops import tracker_access
    st = tracker.init(cap)
    for it in range(4):
        keys = jnp.asarray(RNG.integers(0, 4 * cap, batch), jnp.int32)
        locs = jnp.asarray(RNG.integers(0, 2, batch), jnp.int8)
        valid = jnp.asarray(RNG.random(batch) > 0.1)
        ref = tracker_access(st, keys, locs, valid, backend="reference")
        out = tracker_access(st, keys, locs, valid, backend="pallas",
                             tile=tile)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        st = ref


# -------------------------------------------------------------- msc score

def test_msc_score_kernel():
    from repro.kernels.msc_score.ops import score_candidates
    nb, k = 64, 8
    lo = jnp.asarray(RNG.integers(0, 4096, k), jnp.int32)
    hi = lo + jnp.asarray(RNG.integers(1, 2048, k), jnp.int32)
    t_f = jnp.asarray(RNG.integers(0, 500, k), jnp.int32)
    bf = jnp.asarray(RNG.integers(0, 100, nb), jnp.int32)
    bs = jnp.asarray(RNG.integers(0, 400, nb), jnp.int32)
    bo = jnp.asarray(RNG.integers(0, 50, nb), jnp.int32)
    bh = jnp.asarray(RNG.integers(0, 30, (nb, 4)), jnp.int32)
    pr = jnp.asarray([0.1, 0.4, 0.9, 1.0], jnp.float32)
    r1 = score_candidates(lo, hi, t_f, bf, bs, bo, bh, pr,
                          bucket_width=8192 // nb, backend="reference")
    r2 = score_candidates(lo, hi, t_f, bf, bs, bo, bh, pr,
                          bucket_width=8192 // nb, backend="pallas")
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-5)


def test_msc_score_kernel_matches_core_scoring():
    """Kernel == msc.approx_score used by the live compaction path."""
    from repro.core import PrismDB, TierConfig, mapper, msc, tracker
    from repro.kernels.msc_score.ops import score_candidates
    cfg = TierConfig(key_space=1 << 12, fast_slots=128, slow_slots=1 << 10,
                     value_width=1, max_runs=32, run_size=64,
                     bloom_bits_per_run=1 << 10, tracker_slots=512,
                     n_buckets=16, pin_threshold=0.1)
    db = PrismDB(cfg, seed=0)
    for _ in range(10):
        db.put(RNG.integers(0, cfg.key_space, 64).astype(np.int32))
    state = db.state
    cand = msc.candidate_ranges(state, cfg, jax.random.PRNGKey(0))
    hist = tracker.clock_histogram(state.tracker)
    probs = mapper.pin_probabilities(hist, jnp.float32(cfg.pin_threshold))
    bhist = msc.bucket_clock_hist(state, cfg)
    want = jax.vmap(lambda lo, hi, tf: msc.approx_score(
        state, cfg, lo, hi, tf, bhist, probs))(cand.lo, cand.hi, cand.t_f)
    got = score_candidates(cand.lo, cand.hi, cand.t_f, state.bucket_fast,
                           state.bucket_slow, state.bucket_overlap, bhist,
                           probs, bucket_width=cfg.key_space // cfg.n_buckets,
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)


# ----------------------------------------------- engine-level backend parity

def _parity_db(backend):
    from repro.core import PrismDB, TierConfig, policy
    cfg = TierConfig(key_space=1 << 12, fast_slots=256, slow_slots=1 << 12,
                     value_width=2, max_runs=32, run_size=128,
                     bloom_bits_per_run=1 << 10, tracker_slots=409,
                     n_buckets=16, pin_threshold=0.3)
    pol = policy.PolicyConfig(epoch_ops=256, cooldown_ops=1024,
                              read_heavy_frac=0.5, slow_tracked_frac=0.2,
                              detect_ops=256)
    db = PrismDB(cfg, seed=0, pol_cfg=pol, backend=backend)
    r = np.random.default_rng(7)
    for _ in range(4):
        db.put(r.integers(0, cfg.key_space, 128).astype(np.int32))
    return db


@pytest.mark.parametrize("kind", ["A", "E"])
def test_engine_backend_parity_ycsb(kind):
    """The fused engine under backend='pallas' (interpret) must be BIT-
    identical to the reference backend on a seeded YCSB segment: same
    EngineState counters, same tier occupancy, same per-step results.
    The kernels are exact reimplementations (integer/copy semantics plus
    an argmax-stable scoring pass), so no tolerance is allowed."""
    import jax
    from repro import workloads as W

    out = {}
    for backend in ("reference", "pallas"):
        db = _parity_db(backend)
        stats = db.run_workload(W.ycsb(kind), n_batches=16, batch=128)
        out[backend] = (db, stats)

    db_r, st_r = out["reference"]
    db_p, st_p = out["pallas"]
    # compactions must actually have fired, else the parity is vacuous
    assert db_r.counters["compactions"] > 0
    assert db_r.counters == db_p.counters
    for a, b in zip(jax.tree.leaves(st_r), jax.tree.leaves(st_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # full tier state (pools, indexes, runs, blooms, tracker, buckets)
    for a, b in zip(jax.tree.leaves(db_r.state), jax.tree.leaves(db_p.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert db_r.occupancy() == db_p.occupancy()
    # get results on a probe batch
    probe = np.arange(0, 1 << 12, 13, dtype=np.int32)[:128]
    for (va, fa, sa), (vb, fb, sb) in [(db_r.get(probe), db_p.get(probe))]:
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))


def test_embedding_store_compact_backend_parity():
    """Movement replay through the tier_compact kernels == jnp mirror on a
    real compaction's Movement (the embedding row store payload)."""
    import jax
    from repro.core import embedding_store as es
    cfg = es.EmbedStoreConfig(vocab=4096, dim=32, fast_rows=512)
    state0 = es.init(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, 256), jnp.int32)
    state0, _ = es.prepare_batch(state0, cfg, toks)
    outs = [es.compact(state0, cfg, jax.random.PRNGKey(1), backend=b)[0]
            for b in ("reference", "pallas")]
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_kv_compact_backend_parity():
    """The paged-KV mirror wires 8 pool fields (k/v/kmax/kmin x fast/slow)
    through apply_movement_pools on the pallas branch — every field must
    bit-match the jnp mirror on real compaction Movements."""
    import jax
    from repro.core import paged_kv
    cfg = paged_kv.PagedKVConfig(n_layers=2, kv_heads=2, head_dim=8,
                                 page_tokens=4, fast_pages=8,
                                 slow_pages=256, max_seqs=2,
                                 max_pages_per_seq=32, topk_pages=4,
                                 dtype="float32")
    state0 = paged_kv.init(cfg)
    for sid in range(2):
        k_seq = jnp.asarray(RNG.normal(size=(2, 32, 2, 8)), jnp.float32)
        v_seq = jnp.asarray(RNG.normal(size=(2, 32, 2, 8)), jnp.float32)
        state0 = paged_kv.bulk_insert(state0, cfg, jnp.int32(sid), k_seq,
                                      v_seq, jnp.int32(26))
    outs = []
    for b in ("reference", "pallas"):
        st = state0
        for i in range(3):   # run creation, then slow-survivor merges
            st, _ = paged_kv.compact(st, cfg, jax.random.PRNGKey(i),
                                     backend=b)
        outs.append(st)
    for name, a, b in zip(outs[0]._fields, outs[0], outs[1]):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)


def test_apply_movement_pools_axis():
    """Pool-axis payloads (paged-KV layout [L, P, ...]) ride the same
    movers: apply_movement_pools == apply_movement_rows on the flattened
    rows."""
    from repro.core.compaction import Movement
    from repro.kernels.tier_compact.ops import (apply_movement_pools,
                                                apply_movement_rows)
    L, P, S, T, M = 2, 8, 16, 64, 10
    fp = jnp.asarray(RNG.normal(size=(L, P, T)), jnp.float32)
    sp = jnp.asarray(RNG.normal(size=(L, S, T)), jnp.float32)
    p_dst = np.concatenate([RNG.permutation(P),
                            np.zeros(max(M - P, 0), np.int64)])[:M]
    mv = Movement(
        m_src_tier=jnp.asarray(RNG.integers(0, 2, M), jnp.int32),
        m_src_slot=jnp.asarray(RNG.integers(0, P, M), jnp.int32),
        m_dst_slot=jnp.asarray(RNG.permutation(S)[:M], jnp.int32),
        m_valid=jnp.asarray(RNG.random(M) > 0.3),
        p_src_slot=jnp.asarray(RNG.integers(0, S, M), jnp.int32),
        p_dst_slot=jnp.asarray(p_dst, jnp.int32),
        p_valid=jnp.asarray((RNG.random(M) > 0.5) & (np.arange(M) < P)))
    got_f, got_s = apply_movement_pools(fp, sp, mv, pool_axis=1,
                                        backend="pallas")
    ref_f, ref_s = apply_movement_rows(
        jnp.swapaxes(fp, 0, 1).reshape(P, -1),
        jnp.swapaxes(sp, 0, 1).reshape(S, -1), mv, backend="reference")
    np.testing.assert_array_equal(
        np.asarray(jnp.swapaxes(got_f, 0, 1).reshape(P, -1)),
        np.asarray(ref_f))
    np.testing.assert_array_equal(
        np.asarray(jnp.swapaxes(got_s, 0, 1).reshape(S, -1)),
        np.asarray(ref_s))


# ------------------------------------------------------ interpret resolution

def test_interpret_autoresolves_by_platform():
    from repro.core import backend as backend_mod
    assert backend_mod.resolve_interpret(None, platform="cpu") is True
    assert backend_mod.resolve_interpret(None, platform="tpu") is False
    assert backend_mod.resolve_interpret(None, platform="gpu") is False
    assert backend_mod.resolve_interpret(False, platform="cpu") is False


def test_interpret_forced_on_accelerator_warns_once():
    """Forcing the interpreter on an accelerator is refused, every time
    (it used to warn once and discard the hardware)."""
    from repro.core import backend as backend_mod
    for _ in range(2):
        with pytest.raises(ValueError, match="interpret=True"):
            backend_mod.resolve_interpret(True, platform="tpu")
    assert backend_mod.resolve_interpret(True, platform="cpu") is True


def test_unknown_backend_rejected():
    from repro.core import PrismDB, TierConfig, backend as backend_mod
    with pytest.raises(ValueError):
        backend_mod.check("cuda")
    cfg = TierConfig(key_space=1 << 10, fast_slots=64, slow_slots=256,
                     value_width=1, max_runs=8, run_size=32,
                     bloom_bits_per_run=256, tracker_slots=128, n_buckets=8)
    with pytest.raises(ValueError):
        PrismDB(cfg, backend="cuda")


# ------------------------------------------------------------- recurrences

@pytest.mark.parametrize("b,h,t,d,chunk", [(2, 2, 37, 16, 16),
                                           (1, 4, 64, 32, 32)])
def test_rwkv6_scan(b, h, t, d, chunk):
    from repro.kernels.rwkv6_scan.ops import wkv
    r = jnp.asarray(RNG.normal(size=(b, h, t, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, t, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, t, d)), jnp.float32)
    w = jnp.asarray(RNG.random((b, h, t, d)) * 0.5 + 0.4, jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, d)), jnp.float32)
    r1 = wkv(r, k, v, w, u, backend="reference")
    r2 = wkv(r, k, v, w, u, backend="pallas", chunk=chunk)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=1e-4)


@pytest.mark.parametrize("bb,t,di,n", [(2, 29, 32, 8), (1, 64, 64, 16)])
def test_mamba_scan(bb, t, di, n):
    from repro.kernels.mamba_scan.ops import selective_scan
    x = jnp.asarray(RNG.normal(size=(bb, t, di)), jnp.float32)
    dt = jnp.asarray(RNG.random((bb, t, di)) * 0.1, jnp.float32)
    A = jnp.asarray(-RNG.random((di, n)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(bb, t, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(bb, t, n)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(di,)), jnp.float32)
    r1 = selective_scan(x, dt, A, B, C, D, backend="reference")
    r2 = selective_scan(x, dt, A, B, C, D, backend="pallas", block_d=16,
                        chunk=16)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=1e-4)
