#!/usr/bin/env python3
"""Smoke run of the tiered store on TPU, through its public facade.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # PartitionedDB over a 4-chip mesh

One chip: YCSB core workload A (``workloads/workloada``: read 0.5,
update 0.5, zipfian request distribution, theta 0.99) over 2^22 records
of 1 KiB (CoreWorkload's default 10 fields x 100 B, stored as 256 f32
lanes), with a fast tier of records/8 (paper §7).  It loads every record
by puts in scrambled order (``wdist="hashed"``: the insert pointer's
keys, permuted, as YCSB's default ``insertorder=hashed`` loads), fused
into ``PrismDB.run_workload`` segments, runs YCSB-A segments and one
``scan_ops`` batch under the Pallas backend, and reads a seeded sample
of records back; then it runs one seeded YCSB-A and
YCSB-E segment at the benchmark's default size under the reference and
the Pallas backend and requires bit-equal results.

Four chips (``--chips 4``): the same records as four PrismDB partitions
(paper §4.1) sharded over a 4-device mesh, loaded by routed puts, then
a multi-tenant YCSB-A segment and a routed read-back; and a bit-parity
check of the mesh path against the one-device vmap path.

Every time printed is a smoke reading of one run, not a benchmark.  The
script exits non-zero, without a result line, when JAX finds no TPU,
when the repository's sources are not beside it, or when a check fails.
Its last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
BATCH = 1024            # ops per client batch
SEGMENT = 512           # batches per fused run_workload dispatch
YCSB_SEGMENTS = 2
READBACK = 65536        # records read back after the run
SCAN_LEN = 32           # longest scan a lane asks for (the scan window)
PARITY_BATCHES = 32


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in self.EVENTS:
            self.total += secs


def deployment_cfg(H, records: int, partitions: int = 1):
    """``harness.make_cfg``'s scaling at ``records`` per partition, with
    1 KiB records; the key space spans every partition's records."""
    return H.make_cfg(key_space=records * partitions,
                      fast_slots=records // 8, slow_slots=records,
                      value_width=256, max_runs=max(records // 1024, 64),
                      tracker_slots=records // 10)


def state_gib(tree, jax) -> float:
    return sum(x.nbytes for x in jax.tree.leaves(tree)) / 2**30


def describe(cfg, records: int, partitions: int) -> None:
    log(f"config: YCSB workloada (read 0.5, update 0.5, zipfian theta "
        f"0.99); {records} records x 1 KiB per partition x {partitions} "
        f"partition(s) (value_width={cfg.value_width} f32 lanes); "
        f"fast_slots={cfg.fast_slots} (records/8, paper §7), "
        f"slow_slots={cfg.slow_slots}, tracker_slots={cfg.tracker_slots}, "
        f"max_runs={cfg.max_runs}, run_size={cfg.run_size}, "
        f"n_buckets={cfg.n_buckets}")
    log(f"reduced: recordcount 100000000 -> {records * partitions}, "
        f"because every tier lives in HBM until a host tier exists")


def memory_line(jax, dev) -> str:
    st = dev.memory_stats() or {}
    return (f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"({st.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB of "
            f"{st.get('bytes_limit', 0) / 2**30:.3f} GiB)")


def readback(get, keys: np.ndarray, batch: int,
             routed: bool = False) -> tuple[int, int]:
    """Get the (distinct) ``keys`` in batches; count misses and records
    whose value lanes are not all the key (the value every put of this
    run wrote).  ``routed`` results come back in partition lanes, not in
    key order: a found record is identified by its value."""
    misses = wrong = 0
    for i in range(0, len(keys), batch):
        k = keys[i:i + batch]
        vals, found = (np.asarray(x) for x in get(k)[:2])
        if not routed:
            misses += int((~found).sum())
            wrong += int((found & ~(vals == k[:, None].astype(np.float32))
                          .all(axis=1)).sum())
            continue
        got = vals.reshape(-1, vals.shape[-1])[found.reshape(-1)]
        good = (got == got[:, :1]).all(axis=1) & np.isin(got[:, 0], k)
        misses += len(k) - len(np.intersect1d(got[good, 0], k))
        wrong += int((~good).sum())
    return misses, wrong


# ---------------------------------------------------------------- one chip

def run_one_chip(args, jax, clock) -> None:
    from benchmarks import harness as H
    from repro import workloads as W
    dev = jax.devices()[0]
    records = 1 << args.log2_records
    cfg = deployment_cfg(H, records)
    describe(cfg, records, 1)
    db = H.make_system("prism", cfg, seed=SEED, backend="pallas")
    log(f"engine state: {state_gib(db.estate, jax):.3f} GiB on one "
        f"{dev.device_kind}; after init {memory_line(jax, dev)}")
    db.reset_workload(seed=SEED)

    # ---- load: a put of every record, scrambled order, fused segments ----
    # (a sequential load compacts several times as often, and its rate
    # per put grows with the number of records)
    load = W.spec(read=0.0, wdist="hashed")
    n_load = records // BATCH
    check(n_load % SEGMENT == 0 or n_load < SEGMENT,
          f"{n_load} load batches do not split into {SEGMENT}-batch "
          "segments")
    seg = min(SEGMENT, n_load)
    walls = []
    c0 = clock.total
    for i in range(n_load // seg):
        t = time.perf_counter()
        db.run_workload(load, seg, BATCH)
        jax.block_until_ready(db.estate)
        walls.append(time.perf_counter() - t)
        log(f"load segment {i + 1} of {n_load // seg}: wall {walls[-1]:.3f}"
            f" s; compactions={db.counters['compactions']}")
    ctr = db.counters
    log(f"load: {n_load * BATCH} puts in {len(walls)} segments of {seg} x "
        f"{BATCH}; wall {sum(walls):.3f} s (first segment incl. trace+"
        f"lower+compile {clock.total - c0:.3f} s); compactions="
        f"{ctr['compactions']}; {memory_line(jax, dev)}")
    check(ctr["puts"] == records, f"load counted {ctr['puts']} puts")
    check(ctr["rate_limited"] == 0, f"{ctr['rate_limited']} merged rows "
          "found no free slot")

    # ---- the fused segment holds compiled kernels ------------------------
    # (the same program the load ran: the persistent cache serves it)
    from repro.core.backend import resolve_interpret
    t = time.perf_counter()
    text = W.jit_run_schedule(db.ecfg, seg, BATCH).lower(
        db.estate, W.init_gen(records), jax.random.PRNGKey(SEED),
        W.as_schedule(load, seg), t0=0).compile().as_text()
    n_kernels = text.count('custom_call_target="tpu_custom_call"')
    interpret = resolve_interpret(db.ecfg.interpret)
    log(f"kernels: compiled segment holds {n_kernels} tpu_custom_call "
        f"ops (backend={db.ecfg.backend}, interpret resolved to "
        f"{interpret}; lower+compile {time.perf_counter() - t:.3f} s)")
    check(n_kernels > 0 and not interpret,
          "the pallas segment holds no compiled kernel")

    # ---- YCSB-A ----------------------------------------------------------
    walls = []
    gets = found = 0
    for _ in range(YCSB_SEGMENTS):
        t = time.perf_counter()
        st = db.run_workload(W.ycsb("A"), seg, BATCH)
        jax.block_until_ready(db.estate)
        walls.append(time.perf_counter() - t)
        kind, fnd = np.asarray(st.kind), np.asarray(st.found)
        gets += int((kind == 1).sum()) * BATCH
        found += int(fnd[kind == 1].sum())
    n_ops = YCSB_SEGMENTS * seg * BATCH
    log(f"ycsb-a: {n_ops} ops in {YCSB_SEGMENTS} segments, segment wall "
        f"{[round(w, 3) for w in walls]} s ({n_ops / sum(walls):.0f} "
        f"ops/s smoke reading); gets {gets}, found {found}; "
        f"compactions={db.counters['compactions']}")
    check(found == gets, f"YCSB-A gets found {found} of {gets} records")

    # ---- scans -----------------------------------------------------------
    rng = np.random.default_rng(SEED)
    starts = rng.integers(0, records - SCAN_LEN, BATCH).astype(np.int32)
    lens = rng.integers(1, SCAN_LEN + 1, BATCH).astype(np.int32)
    t = time.perf_counter()
    got = np.asarray(db.scan_ops(starts, lens))
    wall = time.perf_counter() - t
    keys, live = (np.asarray(x) for x in db.scan(int(starts[0]), SCAN_LEN))
    want = np.arange(starts[0], starts[0] + SCAN_LEN)
    log(f"scan: {BATCH} lanes, {int(got.sum())} keys returned of "
        f"{int(lens.sum())} asked ({wall:.3f} s incl. compile); scan from "
        f"{starts[0]} returns {SCAN_LEN} consecutive keys: "
        f"{bool(live.all() and (keys == want).all())}")
    check((got == lens).all(), "a scan lane returned fewer keys than asked")
    check(live.all() and (keys == want).all(),
          "a scan did not return consecutive live keys")

    # ---- read-back: no acknowledged write is lost ------------------------
    sample = rng.choice(records, min(READBACK, records),
                        replace=False).astype(np.int32)
    t = time.perf_counter()
    misses, wrong = readback(db.get, sample, BATCH)
    log(f"read-back: {len(sample)} seeded records, {misses} misses, {wrong} "
        f"wrong values ({time.perf_counter() - t:.3f} s); "
        f"{memory_line(jax, dev)}")
    check(misses == 0 and wrong == 0, "read-back lost records")
    del db

    run_backend_parity(jax, H, W)


def run_backend_parity(jax, H, W) -> None:
    """YCSB-A then YCSB-E at make_cfg's default size, reference vs pallas:
    counters, pools and per-step results must be bit-equal."""
    cfg = H.make_cfg()
    out = {}
    for backend in ("reference", "pallas"):
        db = H.make_system("prism", cfg, seed=SEED, backend=backend)
        db.reset_workload(seed=SEED)
        stats = [db.run_workload(W.ycsb(k), PARITY_BATCHES, BATCH)
                 for k in ("A", "E")]
        probe = np.arange(0, cfg.key_space, 7, dtype=np.int32)[:BATCH]
        out[backend] = (db.counters, jax.device_get(stats),
                        jax.device_get(db.state),
                        jax.device_get(db.get(probe)))
    (c_r, s_r, t_r, g_r), (c_p, s_p, t_p, g_p) = out["reference"], \
        out["pallas"]
    same = lambda a, b: all(np.array_equal(x, y) for x, y in
                            zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    ok = {"counters": c_r == c_p, "step results": same(s_r, s_p),
          "tier state": same(t_r, t_p), "gets": same(g_r, g_p)}
    log(f"parity reference vs pallas (make_cfg default, {cfg.key_space} "
        f"keys, YCSB-A + YCSB-E, {PARITY_BATCHES} x {BATCH} each): "
        + ", ".join(f"{k} {'bit-equal' if v else 'DIFFER'}"
                    for k, v in ok.items())
        + f"; compactions={c_r['compactions']}")
    check(c_r["compactions"] > 0, "no compaction ran: parity is vacuous")
    check(all(ok.values()), "pallas backend differs from reference")


# -------------------------------------------------------------- four chips

def run_four_chips(args, jax, clock) -> None:
    from benchmarks import harness as H
    from repro import workloads as W
    from repro.core.db import PART_AXIS, PartitionedDB
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX sees "
          f"{len(devs)}")
    records = (1 << args.log2_records) // 4          # per partition
    cfg = deployment_cfg(H, records, partitions=4)
    describe(cfg, records, 4)
    db = PartitionedDB(cfg, n_partitions=4, seed=SEED, mesh="auto")
    check(db.mesh is not None and db.mesh.shape[PART_AXIS] == 4,
          f"PartitionedDB resolved mesh {db.mesh}, not 4 devices")
    log(f"mesh: {db.mesh.shape[PART_AXIS]} devices on axis '{PART_AXIS}', "
        f"{db.lp} partition(s) per device; engine state "
        f"{state_gib(db.estate, jax):.3f} GiB in all; after init "
        f"{memory_line(jax, devs[0])}")

    # ---- load: routed puts of every record, in scrambled order -----------
    # (in key order each partition would take a sequential load; a
    # partition's share of one batch must fit its fast tier)
    batch = 4 * min(4096, cfg.fast_slots // 2)
    keys = np.random.default_rng(SEED).permutation(cfg.key_space) \
        .astype(np.int32)
    t = time.perf_counter()
    c0 = clock.total
    for i in range(0, cfg.key_space, batch):
        db.put(keys[i:i + batch])
    jax.block_until_ready(db.estate)
    ctr = H.merged_counters(db)
    log(f"load: {cfg.key_space} routed puts in batches of {batch}: wall "
        f"{time.perf_counter() - t:.3f} s (incl. trace+lower+compile "
        f"{clock.total - c0:.3f} s); puts={ctr['puts']} dropped="
        f"{db.dropped} compactions={ctr['compactions']}; "
        f"{memory_line(jax, devs[0])}")
    check(db.dropped == 0, f"{db.dropped} routed puts were dropped")
    check(ctr["puts"] == cfg.key_space, f"load counted {ctr['puts']} puts")
    check(ctr["rate_limited"] == 0, f"{ctr['rate_limited']} merged rows "
          "found no free slot")

    # ---- multi-tenant YCSB-A, one dispatch across the mesh ---------------
    # every tenant draws keys from the whole key space, so its partition
    # also takes updates of keys it does not own: the segment is kept
    # short enough that they fit beside its own records
    db.reset_workload(seed=SEED)
    seg = min(256, max(records >> 14, 1))
    t = time.perf_counter()
    c0 = clock.total
    db.run_workload(W.ycsb("A"), seg, BATCH)
    jax.block_until_ready(db.estate)
    first = time.perf_counter() - t
    t = time.perf_counter()
    db.run_workload(W.ycsb("A"), seg, BATCH)
    jax.block_until_ready(db.estate)
    log(f"ycsb-a multi-tenant: 2 segments of {seg} x {BATCH} per tenant; "
        f"first {first:.3f} s (incl. trace+lower+compile "
        f"{clock.total - c0:.3f} s), second {time.perf_counter() - t:.3f}"
        f" s; compactions={H.merged_counters(db)['compactions']}")

    # ---- routed read-back -------------------------------------------------
    rng = np.random.default_rng(SEED)
    sample = rng.choice(cfg.key_space, min(READBACK, cfg.key_space),
                        replace=False).astype(np.int32)
    misses, wrong = readback(db.get, sample, 4 * BATCH, routed=True)
    log(f"read-back: {len(sample)} seeded records, routed: {misses} misses, "
        f"{wrong} wrong values; dropped={db.dropped}; "
        f"{memory_line(jax, devs[0])}")
    check(misses == 0 and wrong == 0, "read-back lost records")
    check(db.dropped == 0, f"{db.dropped} routed keys were dropped")
    del db

    run_mesh_parity(jax, H, W)


def run_mesh_parity(jax, H, W) -> None:
    """The same seeded routed client batches and multi-tenant segment on
    4 partitions over the 4-device mesh and vmapped on one device: state,
    counters and drop counts must be bit-equal (the partition-scale-
    parity check, across real chips)."""
    from repro.core.db import PartitionedDB
    cfg = H.make_cfg()
    out = []
    for mesh in ("auto", None):
        db = PartitionedDB(cfg, n_partitions=4, seed=SEED, mesh=mesh)
        rng = np.random.default_rng(SEED)
        db.reset_workload(seed=SEED)
        for _ in range(4):
            db.put(rng.integers(0, cfg.key_space, 4 * BATCH)
                   .astype(np.int32))
            db.get(rng.integers(0, cfg.key_space, 4 * BATCH)
                   .astype(np.int32))
        db.run_workload(W.ycsb("A"), PARITY_BATCHES, BATCH)
        out.append((jax.device_get(db.estate), db.counters,
                    db.dropped_per_partition))
    (e_m, c_m, d_m), (e_v, c_v, d_v) = out
    same = all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(e_m), jax.tree.leaves(e_v)))
    log(f"parity mesh (4 devices) vs vmap (1 device), 4 partitions at "
        f"make_cfg default: engine state {'bit-equal' if same else 'DIFFER'}"
        f", counters {'equal' if c_m == c_v else 'DIFFER'}, drops "
        f"{d_m} vs {d_v}; compactions={sum(c_m['compactions'])}")
    check(sum(c_m["compactions"]) > 0, "no compaction ran: parity is "
          "vacuous")
    check(same and c_m == c_v and d_m == d_v,
          "the mesh path differs from the vmap path")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip smoke (default); 4: only the "
                         "PartitionedDB mesh path and its parity check")
    ap.add_argument("--log2-records", type=int, default=22,
                    help="records in all, log2 (default 22: 4M x 1 KiB); "
                         "--chips 4 splits them over 4 partitions")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(HERE, "src", "repro")) or \
            not os.path.isdir(os.path.join(HERE, "benchmarks")):
        print("chip_smoke: the repository (src/repro, benchmarks/) is not "
              f"beside this script in {HERE}", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{dev.platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache(HERE)}")
    log("timings below are smoke readings of one run, not a benchmark")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    try:
        (run_four_chips if args.chips == 4 else run_one_chip)(args, jax,
                                                              clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total: {time.perf_counter() - t0:.3f} s, of which trace+lower+"
        f"compile {clock.total:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
