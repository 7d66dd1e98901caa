"""A cell added as data alone, and ``correct`` against a broken store.

The runs here drive the whole harness (set-up, load, window, read-back,
comparison) on the CPU at a small size; they skip only its look for a
chip.  Each fault breaks the timed path from the first window batch on,
and each has to make ``correct`` come out false."""
import os
import subprocess
import sys
import time

import pytest

from perfbench import bench, control, harness


class Broken:
    """The store, with ``put`` or ``get`` broken after ``after`` calls
    (the set-up's load, its get pass and the warm-up batches)."""

    def __init__(self, db, fault: str, after: int):
        self.db, self.fault, self.after, self.calls = db, fault, after, 0

    def __getattr__(self, name):
        return getattr(self.db, name)

    def _armed(self) -> bool:
        self.calls += 1
        return self.calls > self.after

    def put(self, keys, vals):
        if not self._armed():
            return self.db.put(keys, vals)
        if self.fault == "unchanged":           # state returned unchanged
            return None
        if self.fault == "half":                # half the batch left out
            h = len(keys) // 2
            return self.db.put(keys[:h], vals[:h])
        return self.db.put(keys, vals)

    def get(self, keys):
        vals, found, src = self.db.get(keys)
        if self._armed() and self.fault == "altered":
            vals = vals.at[:, 7].add(1.0)       # an answer altered
        return vals, found, src


def run(root, store_wrap=None, seed=2**31 + 5, seconds=1.0):
    cell = bench.load_cell("tiny-a", root=str(root),
                           here=str(root / "perfbench"))
    db = harness.build_store(cell.config)
    store = store_wrap(db) if store_wrap else db
    return cell, *harness.run_cell(cell, seed, seconds, False,
                                   time.perf_counter(), store=store)


def test_a_cell_of_new_data_files_is_found_and_listed(tiny_root):
    assert "tiny-a" in bench.cells(str(tiny_root))
    cell = bench.load_cell("tiny-a", root=str(tiny_root),
                           here=str(tiny_root / "perfbench"))
    assert cell.config["tier"]["key_space"] == 4096
    assert cell.traffic["readback_batches"] == 8
    names = [m["name"] for m in cell.per_layer]
    # a metric whose ``workloads`` does not name the cell is not its own
    assert "gets_per_put" in names and "rewrite_per_put" not in names
    assert [m["name"] for m in cell.end_to_end] == ["ops_s", "p99_ms",
                                                    "setup_s"]
    read = bench.metric_reader("gets_per_put",
                               here=str(tiny_root / "perfbench"))
    assert read(harness.Run(puts=4, gets=2)) == 0.5


def test_a_sound_run_is_correct_and_reports_its_metrics(tiny_root):
    cell, r, check = run(tiny_root)
    assert check.correct, check.examples
    assert check.answers > 0 and check.rows_compared > 0
    assert r.ops == 128 * len(r.kinds) and r.puts and r.gets
    assert r.window_compiles == 0
    # set-up ran warm-up batches after the load and its get pass
    assert r.counters0["gets"] + r.counters0["puts"] > 4096 + 128
    here = str(tiny_root / "perfbench")
    for m in cell.end_to_end:
        assert bench.metric_reader(m["name"], here=here)(r) > 0
    per = {m["name"]: bench.metric_reader(m["name"], here=here)(r)
           for m in cell.per_layer}
    # no trace on a run without --trace: trace readers give nothing
    assert per["device_idle_share"] is None and per["pallas_ms"] is None
    assert per["facade_call_us"] > 0 and per["compactions_per_kop"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_a_broken_timed_path_is_not_correct(tiny_root, fault):
    setup_calls = 4096 // 128 + 1 + 4
    _, _, check = run(tiny_root, lambda db: Broken(db, fault, setup_calls))
    assert not check.correct
    if fault == "altered":
        assert check.bad_rows > 0
    else:
        assert check.stale_or_lost > 0


def test_the_control_is_not_correct(tiny_root):
    _, _, check = run(tiny_root, control.LaggedPuts)
    assert not check.correct and check.stale_or_lost > 0


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "ycsb-a",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    from perfbench.tests.conftest import ROOT
    p = _run_py(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_without_the_store_exits_nonzero_with_no_result(tiny_root):
    p = _run_py(str(tiny_root))
    assert p.returncode != 0 and p.stdout.strip() == ""
