#!/usr/bin/env python3
"""Micro timings of the index and pool primitives compaction is built on.

    PYTHONPATH=src python benchmarks/micro_probe.py            # smoke sizes
    PYTHONPATH=src python benchmarks/micro_probe.py --log2-slow 16 \
        --log2-fast 13 --tracker 6553                          # small

Default sizes are those of ``chip_smoke.py``'s one-chip deployment: a
slow index of 2^22 entries updated by a 4,096-row merge, a fast index of
2^19 entries updated by a 1,024-op batch, a tracker of 419,430 slots
with 128 buckets.  Each primitive runs once to compile, then five times;
the best and the median wall time after ``block_until_ready`` are
printed, on whatever device JAX picks.  It measures single programs,
not the store: a reading for choosing between forms (sort, scatter,
prefix count), not a benchmark.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")]

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402

from repro.core import utils as U            # noqa: E402

REPS = 5


def bench(name: str, f, *args) -> None:
    f = jax.jit(f)
    t = time.perf_counter()
    jax.block_until_ready(f(*args))
    first = time.perf_counter() - t
    ts = []
    for _ in range(REPS):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t)
    ts.sort()
    print(f"{name}: best {ts[0] * 1e3:.3f} ms, median "
          f"{ts[REPS // 2] * 1e3:.3f} ms (first call {first:.3f} s)",
          flush=True)


def index_case(rng, n: int, b: int):
    """A 3/4-full sorted index of ``n`` entries and a merge of ``b`` keys
    into one key range that drops ``b/2`` live entries there."""
    live = n - n // 4
    keys = np.sort(rng.choice(1 << 30, live, replace=False)).astype(np.int32)
    idx_k = np.concatenate([keys, np.full(n - live, U.PADKEY, np.int32)])
    idx_s = rng.permutation(n).astype(np.int32)
    s, n_drop = live // 2, b // 2
    drop = np.zeros(n, bool)
    drop[idx_s[s:s + n_drop]] = True
    cand = np.setdiff1d(np.arange(keys[s], keys[s + n_drop]), keys)
    ins = np.sort(rng.choice(cand, min(b, len(cand)), replace=False))
    ins = np.concatenate([ins, np.full(b - len(ins), U.PADKEY)]) \
        .astype(np.int32)
    return (jnp.asarray(idx_k), jnp.asarray(idx_s), jnp.asarray(drop),
            jnp.asarray(ins), jnp.arange(b, dtype=jnp.int32),
            jnp.asarray(ins != U.PADKEY), s, n_drop)


def nonzero_alloc(pool_keys, want):
    """``alloc_slots``'s earlier form: free slots by ``jnp.nonzero``."""
    m = want.shape[0]
    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    free = jnp.nonzero(pool_keys < 0, size=m, fill_value=-1)[0]
    return jnp.where(want, free[jnp.clip(rank, 0, m - 1)], -1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log2-slow", type=int, default=22)
    ap.add_argument("--log2-fast", type=int, default=19)
    ap.add_argument("--tracker", type=int, default=419430)
    ap.add_argument("--buckets", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    ns, nf, t = 1 << args.log2_slow, 1 << args.log2_fast, args.tracker

    for n, b in ((ns, 4096), (nf, 1024)):
        k, sl, drop, ins, isl, iv, s, n_drop = index_case(rng, n, b)
        bench(f"merge_index_update N={n} B={b}", U.merge_index_update,
              k, sl, drop, ins, isl, iv)
        bench(f"splice_index_range N={n} B={b}",
              lambda k, sl, ins, isl, iv: U.splice_index_range(
                  k, sl, jnp.int32(s), jnp.int32(n_drop), ins, isl,
                  jnp.sum(iv.astype(jnp.int32)), b),
              k, sl, ins, isl, iv)

    pool = jnp.asarray(np.where(rng.random(ns) < 0.5, -1, 1), jnp.int32)
    want = jnp.asarray(rng.random(4096) < 0.9)
    bench(f"alloc_slots prefix count + search N={ns}", U.alloc_slots,
          pool, want)
    bench(f"alloc_slots via jnp.nonzero N={ns}", nonzero_alloc, pool, want)

    clock = jnp.asarray(rng.integers(0, 4, t), jnp.int32)
    bench(f"clock histogram, 4 counting reductions T={t}",
          lambda c: jnp.sum(c[:, None] == jnp.arange(4), axis=0), clock)
    bench(f"clock histogram, bincount T={t}",
          lambda c: jnp.bincount(c, length=4), clock)
    nc = args.buckets * 4
    code = jnp.asarray(rng.integers(0, nc + 1, t), jnp.int32)
    bench(f"bucket histogram, sort + search T={t} bins={nc}",
          lambda c: jnp.diff(jnp.searchsorted(jnp.sort(c),
                                              jnp.arange(nc + 1))), code)
    bench(f"bucket histogram, bincount T={t} bins={nc}",
          lambda c: jnp.bincount(c, length=nc + 1)[:nc], code)

    big = jnp.asarray(rng.integers(0, 1 << 30, ns), jnp.int32)
    perm = jnp.asarray(rng.permutation(ns), jnp.int32)
    bench(f"argsort N={ns}", jnp.argsort, big)
    bench(f"cumsum N={ns}", jnp.cumsum, big)
    bench(f"scatter of N={ns} random indices", lambda a, i: a.at[i].set(a),
          big, perm)
    bench(f"gather of N={ns} random indices", lambda a, i: a[i], big, perm)


if __name__ == "__main__":
    main()
