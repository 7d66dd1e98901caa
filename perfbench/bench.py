"""Finds a cell's pieces by name: ``BENCHMARK.json`` at the checkout's
root, ``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py`` and ``peaks.json``.  A cell added as data needs
no edit here, nor in the harness: its configuration names the store
(one ``PrismDB``, or ``partitions`` of a ``PartitionedDB``) and the cell
its chips."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's object
    traffic: dict           # the traffic file's object
    end_to_end: list        # BENCHMARK.json metric entries for this cell
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cells(root: str = ROOT) -> list[str]:
    """The names of the cells ``BENCHMARK.json`` lists."""
    return [w["name"] for w in
            _json(os.path.join(root, "BENCHMARK.json"))["workloads"]]


def load_cell(name: str, root: str = ROOT, here: str = HERE) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def use_compile_cache(jax) -> None:
    """Keep JAX's persistent compilation cache at a fixed path in the
    checkout, with every program in it.  No size limit: with one set (as
    an environment may do) JAX evicts by access-time files, and a
    directory holding an entry without one makes every write fail, so
    each run compiles again."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def metric_reader(name: str, here: str = HERE):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = os.path.join(here, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, here: str = HERE) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = _json(os.path.join(here, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; have {sorted(table)}")
    return table[device_kind]
