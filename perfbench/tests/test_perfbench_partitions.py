"""A store with ``partitions`` built from its configuration file over the
cell's chips, and ``run_cell`` on a partitioned facade: the store's
totals, its routing drops and the fullest chip's memory peak."""
import json
import os
import subprocess
import sys
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import bench, harness, reference
from perfbench.tests.conftest import PERFBENCH, ROOT

SEED = 2**31 + 11


def load(root, name="tiny-p4"):
    return bench.load_cell(name, root=str(root), here=str(root / "perfbench"))


class Refuse:
    """Stands in for a facade class that must not be built."""

    def __init__(self, *args, **kwargs):
        raise AssertionError("built a store")


def test_without_partitions_build_store_calls_prismdb_as_before(monkeypatch):
    from repro.core import TierConfig, policy
    from repro.obs.state import ObsConfig
    import repro.core as core
    with open(os.path.join(PERFBENCH, "configs", "ycsb-1kib-1m.json")) as f:
        config = json.load(f)
    calls = []

    class Record:
        def __init__(self, *args, **kwargs):
            calls.append((args, kwargs))

    monkeypatch.setattr(core, "PrismDB", Record)
    monkeypatch.setattr(core, "PartitionedDB", Refuse)
    harness.build_store(config)
    harness.build_store(config, [object()])     # a one-chip cell's device
    want = ((TierConfig(**config["tier"]),),
            {"seed": 0, "pol_cfg": policy.PolicyConfig(**config["policy"]),
             "backend": "pallas", "obs": ObsConfig(**config["obs"])})
    assert calls == [want, want]
    assert [list(kw) for _, kw in calls] == [list(want[1])] * 2


def test_a_partitioned_cell_of_new_data_files_is_found(tiny_root):
    cell = load(tiny_root)
    assert "tiny-p4" in bench.cells(str(tiny_root))
    assert cell.chips == 4 and harness.partitions(cell.config) == 4
    assert harness.partitions(load(tiny_root, "tiny-a").config) == 1


def test_a_partitioned_configuration_on_one_device_is_vmapped(tiny_root):
    from repro.core import PartitionedDB
    db = harness.build_store(load(tiny_root).config, jax.devices()[:1])
    assert isinstance(db, PartitionedDB)
    assert db.p == 4 and db.mesh is None and db.lp == 4
    assert db.cfg.key_space == 4096            # the whole key space


_ON_FOUR = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from perfbench import bench, harness
from repro.core import PartitionedDB
cell = bench.load_cell("tiny-p4", root={tiny!r}, here={here!r})
db = harness.build_store(cell.config, jax.devices()[:cell.chips])
assert isinstance(db, PartitionedDB), type(db)
print(db.p, db.lp, db.mesh.axis_names, db.mesh.shape["part"],
      len(db.estate.steps.sharding.device_set))
"""


def test_a_partitioned_configuration_on_four_devices_is_on_a_part_mesh(
        tiny_root):
    code = _ON_FOUR.format(root=ROOT, src=os.path.join(ROOT, "src"),
                           tiny=str(tiny_root),
                           here=str(tiny_root / "perfbench"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    # 4 partitions, 1 per device, a 'part' axis of 4, state on 4 devices
    assert p.stdout.split("\n")[-2] == "4 1 ('part',) 4 4"


@pytest.mark.parametrize("partitions,n_devices", [
    (6, 4), (2, 4), (1, 4), (0, 1), (2.5, 1), ("4", 1), (True, 1)])
def test_a_partition_count_that_does_not_fit_raises_before_building(
        monkeypatch, tiny_root, partitions, n_devices):
    import repro.core as core
    monkeypatch.setattr(core, "PrismDB", Refuse)
    monkeypatch.setattr(core, "PartitionedDB", Refuse)
    config = dict(load(tiny_root).config, partitions=partitions)
    with pytest.raises(ValueError, match="partitions"):
        harness.build_store(config, [object()] * n_devices)


# ------------------------------------------------------------ run_cell

class Steps(NamedTuple):
    steps: jax.Array


class Routed:
    """A partitioned store as the facade contract asks for one: P
    ``PrismDB`` s behind a host router by ``part_of_key``, answering in
    the batch's order and reporting counters per partition.  With
    ``drop`` it fails to place the last key of its first put batch."""

    def __init__(self, config: dict, p: int, drop: bool = False):
        plain = {k: v for k, v in config.items() if k != "partitions"}
        self.parts = [harness.build_store(plain) for _ in range(p)]
        self.drop = drop
        self.dropped = 0
        self.reported = None

    def _owner(self, keys):
        from repro.core.utils import part_of_key
        return np.array(part_of_key(jnp.asarray(keys, jnp.int32),
                                    len(self.parts)))

    def put(self, keys, vals):
        owner = self._owner(keys)
        if self.drop:
            owner[-1], self.drop = -1, False
            self.dropped += 1
        for i, db in enumerate(self.parts):
            db.put(keys, vals, valid=owner == i)

    def get(self, keys):
        owner = self._owner(keys)
        outs = [db.get(keys, valid=owner == i)
                for i, db in enumerate(self.parts)]
        lane = np.arange(len(keys))
        return tuple(jnp.stack(x)[owner, lane] for x in zip(*outs))

    @property
    def estate(self):
        return Steps(jnp.stack([db.estate.steps for db in self.parts]))

    @property
    def counters(self):
        per = [db.counters for db in self.parts]
        self.reported = {k: [c[k] for c in per] for k in per[0]}
        return self.reported


def plain_sum(values: list):
    """Per-partition values summed, vectors element by element."""
    if isinstance(values[0], list):
        return [plain_sum(list(col)) for col in zip(*values)]
    return sum(values)


class Chip:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def run_routed(root, **kw):
    cell = load(root)
    store = Routed(cell.config, 4, **kw)
    run, check = harness.run_cell(
        cell, SEED, 1.0, False, time.perf_counter(), store=store,
        devices=[Chip({"peak_bytes_in_use": 5}),
                 Chip({"peak_bytes_in_use": 9})])
    return store, run, check


def test_run_cell_drives_a_partitioned_facade_and_sums_its_counters(
        tiny_root):
    store, run, check = run_routed(tiny_root)
    assert check.correct, check.examples
    assert check.numbers() == {"stale_or_lost": {"value": 0, "limit": 0},
                               "bad_rows": {"value": 0, "limit": 0},
                               "dropped": {"value": 0, "limit": 0}}
    assert run.counters1 == {k: plain_sum(v)
                             for k, v in store.reported.items()}
    assert run.counters1["puts"] > run.counters0["puts"] > 4096
    assert isinstance(run.counters1["hits_by_tier"], list)
    # each part saw only its own keys, so no part holds every record
    assert all(0 < v < 4096 for v in store.reported["puts"])
    assert bench.metric_reader("compactions_per_kop")(run) is not None
    assert run.memory_peak_bytes == 9


def test_a_dropped_put_key_fails_the_run(tiny_root):
    _, _, check = run_routed(tiny_root, drop=True)
    assert check.dropped == 1 and not check.correct
    assert check.numbers()["dropped"] == {"value": 1, "limit": 0}
    assert check.failed >= 1


@pytest.mark.parametrize("stats,want", [
    ([{"peak_bytes_in_use": 7}, {"peak_bytes_in_use": 3}], 7),
    ([{"peak_bytes_in_use": 3}, {"peak_bytes_in_use": 7}], 7),
    ([None, {"peak_bytes_in_use": 4}], 4),
    ([{"peak_bytes_in_use": 6}], 6),
])
def test_the_memory_peak_is_the_fullest_devices(stats, want):
    assert harness.memory_peak_bytes([Chip(s) for s in stats]) == want


@pytest.mark.parametrize("dropped,correct,keys", [
    (None, True, ["stale_or_lost", "bad_rows"]),
    (0, True, ["stale_or_lost", "bad_rows", "dropped"]),
    (3, False, ["stale_or_lost", "bad_rows", "dropped"]),
])
def test_drops_are_compared_only_where_the_store_routes(dropped, correct,
                                                          keys):
    check = reference.Check(answers=8, rows_compared=8, dropped=dropped)
    assert check.correct is correct
    assert list(check.numbers()) == keys
    assert check.failed == (dropped or 0)


def test_the_result_line_counts_drops_as_failed():
    from perfbench import run as run_py
    cell = bench.load_cell("ycsb-a")
    r = harness.Run(setup_s=1.0, t0=0.0, t1=1.0, kinds=["get", "put"],
                    latency_s=[0.1, 0.2], call_s=[0.01, 0.01], ops=2048,
                    memory_peak_bytes=9)
    check = reference.Check(answers=8, stale_or_lost=1, rows_compared=8,
                            dropped=2)

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    out = run_py.result_line(cell, r, check, Dev(), 1, False)
    assert out["failed"] == 3 and out["correct"] is False
    assert out["device"]["memory_peak_bytes"] == 9
    assert list(out)[-1] == "compared"
    assert out["compared"]["dropped"] == {"value": 2, "limit": 0}
