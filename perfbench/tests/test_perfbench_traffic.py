"""The benchmark's generator against the repository's zipf and scramble
semantics, and the properties the harness relies on."""
import numpy as np
import pytest

from perfbench import traffic

MIX = {"shares": {"get": 0.5, "put": 0.5}, "kind_block": 10,
       "keys": {"get": {"dist": "zipf", "theta": 0.99},
                "put": {"dist": "uniform"}},
       "batch": 64, "inflight": 2, "readback_batches": 4}


@pytest.mark.parametrize("theta", [0.5, 0.99, 1.0, 1.2])
def test_zipf_and_scramble_match_the_repository(theta):
    from repro.workloads import reference as ref
    rng = np.random.default_rng(12345)
    u = rng.random(4096, dtype=np.float32)
    n = 1 << 14
    ours = traffic.zipf_ranks(u, n, theta)
    np.testing.assert_array_equal(
        ours, ref.ranks_from_uniforms_host(u, n, theta))
    np.testing.assert_array_equal(traffic.scramble(ours, 3, n),
                                  ref.scramble_host(ours, 3, n))


def test_load_is_every_key_once_in_the_hashed_order():
    from repro.workloads import reference as ref
    gen = traffic.Traffic(MIX, 1 << 12, seed=1)
    keys = np.concatenate(list(gen.load_batches()))
    np.testing.assert_array_equal(np.sort(keys), np.arange(1 << 12))
    np.testing.assert_array_equal(
        keys, ref.scramble_host(np.arange(1 << 12), 0, 1 << 12))


def test_same_seed_same_stream_and_kinds_in_exact_blocks():
    a = traffic.Traffic(MIX, 1 << 12, seed=2**31 + 77)
    b = traffic.Traffic(MIX, 1 << 12, seed=2**31 + 77)
    sa = [a.next_batch() for _ in range(40)]
    sb = [b.next_batch() for _ in range(40)]
    assert [k for k, _ in sa] == [k for k, _ in sb]
    for (_, x), (_, y) in zip(sa, sb):
        np.testing.assert_array_equal(x, y)
    kinds = [k for k, _ in sa]
    for i in range(0, 40, 10):
        assert kinds[i:i + 10].count("get") == 5
    c = traffic.Traffic(MIX, 1 << 12, seed=2**31 + 78)
    sc = [c.next_batch() for _ in range(40)]
    assert [k for k, _ in sc] != kinds


def test_writes_are_the_same_for_every_seed_and_reads_are_not():
    a = traffic.Traffic(MIX, 1 << 12, seed=3)
    b = traffic.Traffic(MIX, 1 << 12, seed=4)
    sa = [a.next_batch() for _ in range(40)]
    sb = [b.next_batch() for _ in range(40)]
    puts_a = np.concatenate([k for kind, k in sa if kind == "put"])
    puts_b = np.concatenate([k for kind, k in sb if kind == "put"])
    np.testing.assert_array_equal(puts_a, puts_b)
    gets_a = np.concatenate([k for kind, k in sa if kind == "get"])
    gets_b = np.concatenate([k for kind, k in sb if kind == "get"])
    assert not np.array_equal(gets_a, gets_b)
    assert a.version(0) != b.version(0)


def test_readback_sample_does_not_depend_on_the_window():
    a = traffic.Traffic(MIX, 1 << 12, seed=9)
    b = traffic.Traffic(MIX, 1 << 12, seed=9)
    for _ in range(13):
        b.next_batch()
    ka, kb = a.readback_keys(), b.readback_keys()
    np.testing.assert_array_equal(ka, kb)
    assert len(ka) == 4 * 64 and len(np.unique(ka)) == len(ka)


def test_versions_fit_a_float32_lane():
    gen = traffic.Traffic(MIX, 1 << 12, seed=-5)
    v = [gen.version(t) for t in (0, 1, 5_000_000)]
    assert all(0 <= x < 1 << 24 for x in v) and len(set(v)) == 3


def test_shares_that_do_not_fill_a_block_are_refused():
    bad = dict(MIX, shares={"get": 0.33, "put": 0.67})
    with pytest.raises(ValueError):
        traffic.Traffic(bad, 1 << 12, seed=0)
