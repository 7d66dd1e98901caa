import copy
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
if os.path.join(ROOT, "src") not in sys.path:     # the store under test
    sys.path.insert(0, os.path.join(ROOT, "src"))

# a cell small enough for the CPU: the ycsb configuration's record width
# at 2^12 records, on the reference backend (no Pallas interpreter)
TINY_TIER = {"key_space": 4096, "fast_slots": 512, "slow_slots": 4096,
             "max_runs": 64, "tracker_slots": 409}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout holding a copy of perfbench and a BENCHMARK.json with
    two small cells made only of new data files: ``tiny-a``, and
    ``tiny-p4``, the same configuration as 4 partitions on 4 chips."""
    root = tmp_path / "checkout"
    shutil.copytree(PERFBENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(PERFBENCH, "configs", "ycsb-1kib-1m.json")) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config["name"] = "tiny"
    config["backend"] = "reference"
    config["tier"].update(TINY_TIER)
    (root / "perfbench" / "configs" / "tiny.json").write_text(
        json.dumps(config))
    (root / "perfbench" / "configs" / "tiny-p4.json").write_text(
        json.dumps(dict(config, name="tiny-p4", partitions=4)))
    with open(os.path.join(PERFBENCH, "traffic", "ycsb-a.json")) as f:
        mix = json.load(f)
    # a put batch must fit the fast tier (the store drops what does not)
    mix["batch"] = 128
    mix["readback_batches"] = 8
    mix["warmup_batches"] = 4
    (root / "perfbench" / "traffic" / "tiny-a.json").write_text(
        json.dumps(mix))
    (root / "perfbench" / "metrics" / "gets_per_put.py").write_text(
        "def read(ctx):\n"
        "    return ctx.gets / ctx.puts if ctx.puts else None\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny", "source": "https://example.org/tiny",
        "file": "perfbench/configs/tiny.json", "reduced": ["recordcount"],
        "why": "a test cell"})
    bench["configs"].append({
        "name": "tiny-p4", "source": "https://example.org/tiny",
        "file": "perfbench/configs/tiny-p4.json", "reduced": ["recordcount"],
        "why": "a partitioned test cell"})
    bench["workloads"].append({
        "name": "tiny-a", "config": "tiny", "traffic": "tiny-a",
        "chips": 1, "why": "a test cell"})
    bench["workloads"].append({
        "name": "tiny-p4", "config": "tiny-p4", "traffic": "tiny-a",
        "chips": 4, "why": "a partitioned test cell"})
    bench["per_layer"].append({
        "name": "gets_per_put", "unit": "1", "better": "higher",
        "source": "host_clock", "layer": "facade", "moves": "ops_s",
        "workloads": ["tiny-a"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
