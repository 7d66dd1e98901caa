"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the metrics.

The store is built from the configuration file: one ``PrismDB``, or
with a top-level ``"partitions": P`` above 1 a ``PartitionedDB`` of P
partitions, each with the pools ``tier`` describes, over the cell's
chips (``build_store``).  ``tier.key_space`` is the whole key space in
both cases: the traffic draws its keys from it.

The window drives the store's client facade in a closed loop that keeps
``inflight`` batches outstanding: the next batch is submitted before the
oldest one's result is fetched, since the facade's dispatch is
asynchronous.  An op's latency runs from the start of its batch's facade
call to that batch's result being on the host: a get's values copied
back, a put's step finished on the device.

The facade contract: the harness calls only these, on either facade.
- ``put(keys, vals)``: write row ``vals[i]`` under ``keys[i]``;
- ``get(keys) -> (vals, found, src)``, the answers in the batch's order;
- ``estate``, whose ``steps`` leaf is done once the last put's step is;
- ``counters``: a dict of counters, with a leading partition axis on a
  partitioned store (summed here into the store's totals);
- optionally ``dropped``: the keys a routed batch could not place, so
  far.  Each is a lost write or a lost answer, and fails the run.
``PrismDB`` meets it.  ``PartitionedDB`` does not yet: its ``put(keys)``
stores no payload, and its ``get`` answers in partition layout
(``[P, per_part]``), not in the batch's order.
"""
from __future__ import annotations

import collections
import contextlib
import time
from dataclasses import dataclass, field

import numpy as np

from perfbench import reference, traffic

SAMPLE_ROWS = 16        # full rows compared per get batch, drawn from the seed
TRACE_SECONDS = 4.0     # the traced window of a --trace 1 run, at most


def partitions(config: dict) -> int:
    """The configuration's partition count: ``partitions``, default 1."""
    p = config.get("partitions", 1)
    if isinstance(p, bool) or not isinstance(p, int) or p < 1:
        raise ValueError(f"partitions must be a whole number >= 1, got {p!r}")
    return p


def build_store(config: dict, devices=None):
    """The system under test, as the configuration file states it: a
    ``PrismDB`` on the default device, or with ``partitions`` P > 1 a
    ``PartitionedDB`` over ``devices`` (None: the default device), on a
    1-D ``part`` mesh where there is more than one and on the vmapped
    path where there is one."""
    from repro.core import PrismDB, TierConfig, policy
    from repro.obs.state import ObsConfig
    p = partitions(config)
    n_dev = 1 if devices is None else len(devices)
    if p % n_dev:
        raise ValueError(f"{p} partitions cannot be spread evenly over "
                         f"{n_dev} devices")
    kwargs = dict(seed=int(config["engine_seed"]),
                  pol_cfg=policy.PolicyConfig(**config["policy"]),
                  backend=config["backend"], obs=ObsConfig(**config["obs"]))
    if p == 1:
        return PrismDB(TierConfig(**config["tier"]), **kwargs)
    import jax
    from repro.core import PartitionedDB
    from repro.core.db import PART_AXIS
    mesh = (jax.sharding.Mesh(np.asarray(devices), (PART_AXIS,))
            if n_dev > 1 else None)
    return PartitionedDB(TierConfig(**config["tier"]), n_partitions=p,
                         mesh=mesh, **kwargs)


def store_totals(counters: dict) -> dict:
    """A partitioned store's counters summed over their leading partition
    axis, vectors element by element: the values a one-partition store
    of the same pools would report."""
    return {k: np.asarray(v).sum(axis=0).tolist()
            for k, v in counters.items()}


def memory_peak_bytes(devices) -> int:
    """The largest ``peak_bytes_in_use`` over ``devices``: the fullest
    chip's peak."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclass
class Batch:
    kind: str
    keys: np.ndarray
    submitted: float
    call_s: float
    handle: object
    want: np.ndarray | None = None


@dataclass
class Run:
    """What one run measured; the metric readers take it as ``ctx``."""
    setup_s: float = 0.0
    t0: float = 0.0
    t1: float = 0.0
    kinds: list = field(default_factory=list)
    latency_s: list = field(default_factory=list)
    call_s: list = field(default_factory=list)
    ops: int = 0
    puts: int = 0
    gets: int = 0
    counters0: dict = field(default_factory=dict)   # the store's totals
    counters1: dict = field(default_factory=dict)
    window_compiles: int = 0        # programs compiled inside the window
    trace: dict | None = None       # tracing.reduce's output
    memory_peak_bytes: int | None = None   # of the fullest of the chips

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def counter_delta(self, name: str) -> int:
        return self.counters1[name] - self.counters0[name]

    def latency_quantile(self, q: float, kind: str | None = None):
        """The ``q`` quantile of per-op latency in seconds over the
        window's ops (of ``kind`` only, if given): every op of a batch
        has its batch's latency, and batches are of one size, so it is
        the batches' ``inverted_cdf`` quantile.  None without such ops."""
        lat = [x for k, x in zip(self.kinds, self.latency_s)
               if kind is None or k == kind]
        if not lat:
            return None
        return float(np.quantile(lat, q, method="inverted_cdf"))


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Client:
    """Drives one store with one cell's traffic and keeps the reference
    and the comparison beside it."""

    def __init__(self, store, mix: dict, key_space: int, width: int,
                 seed: int, spans: bool = False):
        import jax
        self.jax = jax
        self.db = store
        self.gen = traffic.Traffic(mix, key_space, seed)
        self.ref = reference.Reference(key_space)
        self.check = reference.Check()
        self.width = width
        self.inflight = int(mix["inflight"])
        self.spans = spans
        self.t = 0                              # global batch index
        self.answers = []                       # kept until compared
        self.sample_rng = np.random.default_rng(
            traffic.seed_sequence(seed).spawn(5)[4])
        self._marker = jax.jit(lambda x: x + 1)

    # ---------------------------------------------------------- one batch
    def submit(self, kind: str, keys: np.ndarray) -> Batch:
        if kind == "put":
            version = self.gen.version(self.t)
            with _span("pb.generate", self.spans):
                vals = reference.rows(keys, version, self.width)
            with _span("pb.put", self.spans):
                t = time.perf_counter()
                self.db.put(keys, vals)
                # a put returns nothing: a dependent scalar finishes when
                # the put's step has
                handle = self._marker(self.db.estate.steps)
                call = time.perf_counter() - t
            self.ref.put(keys, version)
            want = None
        elif kind == "get":
            want = self.ref.expected(keys)
            with _span("pb.get", self.spans):
                t = time.perf_counter()
                vals, found, _ = self.db.get(keys)
                call = time.perf_counter() - t
            handle = (vals, found)
        else:
            raise ValueError(f"unknown op kind {kind!r}")
        self.t += 1
        return Batch(kind, keys, t, call, handle, want)

    def complete(self, b: Batch) -> float:
        """Wait for ``b``'s result on the host; keep what the comparison
        needs; return the time it arrived."""
        with _span("pb.fetch", self.spans):
            if b.kind == "get":
                vals, found = (np.asarray(x) for x in b.handle)
            else:
                self.jax.block_until_ready(b.handle)
            done = time.perf_counter()
        if b.kind == "get":
            idx = self.sample_rng.choice(len(b.keys), min(
                SAMPLE_ROWS, len(b.keys)), replace=False)
            self.answers.append((b.keys, b.want, found, vals[:, :2].copy(),
                                 idx, vals[idx]))
        return done

    def compare(self) -> None:
        """Compare the answers kept so far with the reference."""
        for answer in self.answers:
            self.check.compare(*answer, width=self.width)
        self.answers = []

    # ------------------------------------------------------------- phases
    def load(self) -> None:
        """Put every record once, in scrambled order; then warm the get
        path on the last load batch."""
        last = None
        for keys in self.gen.load_batches():
            last = self.submit("put", keys)
        self.complete(last)
        self.complete(self.submit("get", keys))
        self.compare()

    def warm(self, batches: int) -> None:
        """Run ``batches`` of the cell's own traffic before the window:
        after the load, compaction work per batch climbs for thousands of
        batches, and the window measures the store once it has leveled."""
        pending = collections.deque()
        for _ in range(batches):
            pending.append(self.submit(*self.gen.next_batch()))
            if len(pending) >= self.inflight:
                self.complete(pending.popleft())
        while pending:
            self.complete(pending.popleft())
        self.compare()

    def window(self, run: Run, seconds: float) -> None:
        pending = collections.deque()
        run.t0 = time.perf_counter()
        deadline = run.t0 + seconds
        with _span("pb.window", self.spans):
            while time.perf_counter() < deadline or pending:
                if time.perf_counter() < deadline:
                    with _span("pb.generate", self.spans):
                        kind, keys = self.gen.next_batch()
                    pending.append(self.submit(kind, keys))
                    if len(pending) < self.inflight:
                        continue
                b = pending.popleft()
                done = self.complete(b)
                run.kinds.append(b.kind)
                run.latency_s.append(done - b.submitted)
                run.call_s.append(b.call_s)
                run.t1 = done
        n = self.gen.batch
        run.ops = n * len(run.kinds)
        run.puts = n * run.kinds.count("put")
        run.gets = n * run.kinds.count("get")

    def readback(self) -> None:
        """Read a seeded sample of records after the window; compare
        every row of it in full, and the window's answers."""
        self.compare()
        keys = self.gen.readback_keys()
        n = self.gen.batch
        for i in range(0, len(keys), n):
            k = keys[i:i + n]
            want = self.ref.expected(k)
            vals, found, _ = self.db.get(k)
            vals, found = np.asarray(vals), np.asarray(found)
            self.check.compare(k, want, found, vals[:, :2],
                               np.arange(len(k)), vals, self.width)


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             store=None, devices=None) -> tuple[Run, reference.Check]:
    """Set up, measure and check one run.  ``store`` replaces the system
    under test (the tests hand in a broken one); ``devices`` are the JAX
    devices of the cell's chips: the store is built over them, and the
    fullest one's memory peak is read (None: the default device, and no
    peak read)."""
    import jax
    from perfbench import tracing
    cfg = cell.config
    run = Run()
    db = store if store is not None else build_store(cfg, devices)
    partitioned = partitions(cfg) > 1
    dropped0 = getattr(db, "dropped", None)
    client = Client(db, cell.traffic, int(cfg["tier"]["key_space"]),
                    int(cfg["tier"]["value_width"]), seed, spans=trace)
    client.load()
    client.warm(int(cell.traffic["warmup_batches"]))
    jax.block_until_ready(db.estate)
    run.counters0 = store_totals(db.counters) if partitioned else db.counters
    run.setup_s = time.perf_counter() - t_start

    seconds = min(seconds, TRACE_SECONDS) if trace else seconds
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    with tracing.capture(trace) as captured:
        client.window(run, seconds)
    run.window_compiles = len(compiles)
    run.counters1 = store_totals(db.counters) if partitioned else db.counters
    if devices is not None:
        run.memory_peak_bytes = memory_peak_bytes(devices)
    if trace:
        run.trace = tracing.reduce_file(captured)
    client.readback()
    if dropped0 is not None:
        client.check.dropped = int(db.dropped) - int(dropped0)
    return run, client.check
