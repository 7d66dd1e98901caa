"""Host-side export of the device-resident ``ObsState``.

Everything here runs OUTSIDE jit, at segment boundaries: one
``jax.device_get`` pulls the whole (small, fixed-size) pytree, then
plain numpy turns it into structured dicts and percentile estimates.
The numpy bucket function is a bit-exact mirror of the
device one so the quantile tests can use an exact oracle.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import jax
import numpy as np

from repro.obs.state import EVENT_KIND_NAMES, TRIGGER_NAMES, ObsState

QUANTILES = (0.5, 0.99, 0.999)
QUANTILE_NAMES = {0.5: "p50", 0.99: "p99", 0.999: "p999"}


def bucket_of_us_np(us, n_buckets: int):
    """Numpy mirror of ``state.bucket_of_us``: ceil(log2) read off the
    f32 bit pattern -- integer ops only, so it is bit-identical to the
    device version on every input (no libm involved)."""
    us = np.maximum(np.asarray(us, np.float32), np.float32(1e-6))
    bits = np.asarray(us, np.float32).view(np.int32)
    b = (bits >> 23) - 127 + (bits & 0x7FFFFF != 0).astype(np.int32)
    return np.clip(b, 0, n_buckets - 1)


def bucket_bounds(n_buckets: int):
    """(lo, hi) arrays in us: bucket 0 is (0, 1], bucket b is
    (2^(b-1), 2^b]; the top bucket also absorbs overflow."""
    b = np.arange(n_buckets)
    hi = np.exp2(b).astype(np.float64)
    lo = np.where(b == 0, 0.0, np.exp2(b - 1.0))
    return lo, hi


def quantile_from_hist(hist: np.ndarray, q: float,
                       sums: np.ndarray | None = None) -> float:
    """Estimate the q-quantile of the per-op costs summarised by one
    histogram row: rank = ceil(q * N) (1-based, so p999 of 1000 ops is
    the worst op), find its bucket by cumulative count, interpolate
    linearly inside the bucket.  Returns 0.0 for an empty histogram.

    Without ``sums`` the interpolation assumes a uniform spread over the
    bucket's full (lo, hi] bounds -- which ALIASES nearby distributions:
    log2 buckets are wide, so two workloads whose p50 ops land in the
    same bucket at the same rank-fraction report the identical
    percentile.  ``sums`` (the ``hist_sum`` running per-bucket cost
    totals) de-aliases: the bucket's observed mean ``m = sum / count``
    recentres the uniform model onto the widest sub-interval of
    [lo, hi] whose midpoint is ``m`` -- [lo, 2m - lo] when the mass
    leans low, [2m - hi, hi] when it leans high -- so the estimate
    moves with the distribution while never leaving its bucket (the
    order-statistic oracle bound still holds)."""
    hist = np.asarray(hist, np.int64)
    n = int(hist.sum())
    if n == 0:
        return 0.0
    rank = int(np.ceil(q * n))
    rank = min(max(rank, 1), n)
    cum = np.cumsum(hist)
    b = int(np.searchsorted(cum, rank, side="left"))
    lo, hi = bucket_bounds(hist.shape[0])
    before = int(cum[b - 1]) if b > 0 else 0
    frac = (rank - before) / float(hist[b])
    a, z = float(lo[b]), float(hi[b])
    if sums is not None and hist[b] > 0:
        m = float(np.asarray(sums, np.float64)[b]) / float(hist[b])
        m = min(max(m, a), z)
        a, z = max(a, 2.0 * m - z), min(z, 2.0 * m - a)
    return float(a + (z - a) * frac)


def quantiles_from_hist(hist: np.ndarray,
                        qs: Sequence[float] = QUANTILES,
                        sums: np.ndarray | None = None) -> dict:
    """{"p50": ..., "p99": ..., "p999": ...} for one histogram row (or a
    [kinds, buckets] matrix, which is first summed over kinds); pass the
    matching ``hist_sum`` row as ``sums`` for sub-bucket precision."""
    hist = np.asarray(hist)
    if hist.ndim == 2:
        hist = hist.sum(axis=0)
    if sums is not None:
        sums = np.asarray(sums)
        if sums.ndim == 2:
            sums = sums.sum(axis=0)
    return {QUANTILE_NAMES.get(q, f"p{q}"):
            quantile_from_hist(hist, q, sums) for q in qs}


def snapshot(obs: ObsState) -> dict:
    """One device_get -> plain numpy dict.  Handles both a scalar
    engine's ObsState and a vmapped/stacked one (leading partition dim
    on every leaf): stacked states are merged -- histograms, ring
    positions and event counts by summation (the reason histograms were
    chosen over reservoirs), timelines and event rings kept per
    partition under ``per_partition``.  Mesh-sharded states (the
    ``shard_map`` PartitionedDB path shards the same leading partition
    axis over a device mesh) need no special case: the ``device_get``
    gathers every ``part``-sharded leaf across the mesh into the same
    stacked layout, so vmapped and sharded snapshots are bit-identical
    (pinned by ``tests/test_partitioned_mesh.py``)."""
    host = jax.device_get(obs)
    hist = np.asarray(host.hist)
    stacked = hist.ndim == 3
    t_pos = np.asarray(host.t_pos).reshape(-1)
    ev_count = np.asarray(host.ev_count).reshape(-1)
    hist_sum = np.asarray(host.hist_sum)
    ev_jobs = np.asarray(host.ev_jobs).reshape(-1)
    ev_jobs_b = np.asarray(host.ev_jobs_b)
    ev_jobs_t = np.asarray(host.ev_jobs_t)
    step_comp_hist = np.asarray(host.step_comp_hist)
    snap = {
        "hist": hist.sum(axis=0) if stacked else hist,
        "hist_sum": hist_sum.sum(axis=0) if stacked else hist_sum,
        "t_pos": int(t_pos.sum()),
        "ev_count": int(ev_count.sum()),
        "ev_jobs": int(ev_jobs.sum()),
        "t_pos_per_part": t_pos,
        "ev_count_per_part": ev_count,
        "timeline": np.asarray(host.timeline),
        "ev_step": np.asarray(host.ev_step),
        "ev_trigger": np.asarray(host.ev_trigger),
        "ev_score": np.asarray(host.ev_score),
        "ev_moved": np.asarray(host.ev_moved),
        "ev_superseded": np.asarray(host.ev_superseded),
        "ev_io_us": np.asarray(host.ev_io_us),
        "ev_kind": np.asarray(host.ev_kind),
        "ev_boundary": np.asarray(host.ev_boundary),
        "ev_jobs_b": (ev_jobs_b.sum(axis=0) if ev_jobs_b.ndim == 2
                      else ev_jobs_b),
        "ev_jobs_t": ev_jobs_t.sum(axis=0) if stacked else ev_jobs_t,
        "step_comp_hist": (step_comp_hist.sum(axis=0) if stacked
                           else step_comp_hist),
        "n_partitions": hist.shape[0] if stacked else 1,
    }
    return snap


def hist_delta(after: Mapping, before: Mapping) -> np.ndarray:
    return np.asarray(after["hist"], np.int64) - np.asarray(
        before["hist"], np.int64)


def hist_sum_delta(after: Mapping, before: Mapping) -> np.ndarray:
    """Delta of the per-bucket cost sums between two snapshots (pairs
    with ``hist_delta`` to compute segment-local sums-aware quantiles)."""
    return np.asarray(after["hist_sum"], np.float64) - np.asarray(
        before["hist_sum"], np.float64)


def _ring_order(count: int, length: int) -> np.ndarray:
    """Valid indices of a ring with ``count`` total writes, oldest
    first."""
    if count <= length:
        return np.arange(count)
    start = count % length
    return np.concatenate([np.arange(start, length), np.arange(start)])


def events_table(snap: Mapping) -> list:
    """Compaction events (oldest surviving first) as dicts; for a
    partitioned snapshot, per-partition rings are flattened with a
    ``partition`` field."""
    ev_step = np.asarray(snap["ev_step"])
    if ev_step.ndim == 1:
        ev_step = ev_step[None]
    parts = ev_step.shape[0]
    rows = []
    for p in range(parts):
        def leaf(name):
            a = np.asarray(snap[name])
            return a[p] if a.ndim > 1 else a
        step, trig = leaf("ev_step"), leaf("ev_trigger")
        score, moved = leaf("ev_score"), leaf("ev_moved")
        sup, io = leaf("ev_superseded"), leaf("ev_io_us")
        kind = (leaf("ev_kind") if "ev_kind" in snap
                else np.zeros_like(step))
        bnd = (leaf("ev_boundary") if "ev_boundary" in snap
               else np.zeros_like(step))
        per = np.asarray(snap.get("ev_count_per_part",
                                  snap["ev_count"])).reshape(-1)
        count = int(per[p]) if per.size > 1 else int(snap["ev_count"])
        for i in _ring_order(count, step.shape[0]):
            rows.append({
                "partition": p,
                "step": int(step[i]),
                "trigger": TRIGGER_NAMES[int(trig[i])],
                "kind": EVENT_KIND_NAMES[int(kind[i])],
                "boundary": int(bnd[i]),
                "msc_score": float(score[i]),
                "moved": int(moved[i]),
                "superseded": int(sup[i]),
                "io_us": float(io[i]),
            })
    return rows


def timeline_table(snap: Mapping) -> list:
    """Per-step counter-delta rows (oldest surviving first).  Per-tier
    vector counters appear both expanded ("hits0", "hits1", ...) and as
    the legacy aggregate names ("hits_fast" = tier 0, "hits_slow" = the
    sum of every lower tier, ...), so two-tier consumers keep working
    unchanged against any N."""
    from repro.obs.state import timeline_fields  # lazy: cycle breaker
    tl = np.asarray(snap["timeline"])
    if tl.ndim == 2:
        tl = tl[None]
    n_tiers = (tl.shape[-1] - 13) // 6  # width = 13 + 6*T (see state.py)
    fields = timeline_fields(n_tiers)
    legacy = {"hits_fast": ("hits", 0), "fast_reads": ("reads", 0),
              "fast_writes": ("writes", 0), "hits_slow": ("hits", None),
              "slow_reads": ("reads", None),
              "slow_writes": ("writes", None),
              "comp_reads": ("comp_reads", -1),
              "scan_reads": ("scan_reads", -1)}
    rows = []
    for p in range(tl.shape[0]):
        per = np.asarray(snap.get("t_pos_per_part",
                                  snap["t_pos"])).reshape(-1)
        count = int(per[p]) if per.size > 1 else int(snap["t_pos"])
        for i in _ring_order(count, tl.shape[1]):
            row = {"partition": p}
            row.update({f: int(v) for f, v in zip(fields, tl[p, i])})
            for name, (base, t) in legacy.items():
                vec = [row[f"{base}{j}"] for j in range(n_tiers)]
                row[name] = (vec[0] if t == 0
                             else sum(vec[1:]) if t is None
                             else sum(vec))
            rows.append(row)
    return rows
