#!/usr/bin/env python3
"""Benchmark one cell of the tiered key-value store on the chip.

    python3 perfbench/run.py --workload ycsb-a --seed 7 --seconds 20 \
        --trace 0

From the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are found by name (``BENCHMARK.json``,
``perfbench/configs``, ``perfbench/traffic``, ``perfbench/metrics``).
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: each number the
comparison with the reference decides ``correct`` on, with its limit.
The same numbers end standard error.

The run exits non-zero and prints no result where JAX finds no TPU, or
fewer chips than the cell asks for, or where the store's sources
(``src/repro``) are not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg: str, code: int) -> int:
    print(f"perfbench: {msg}", file=sys.stderr)
    return code


def result_line(cell, run, check, dev, n_devices: int, trace: bool) -> dict:
    from perfbench import bench
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = bench.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # memory_peak_bytes: the peak of the fullest of the cell's chips
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_devices,
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": check.correct, "attempted": run.ops,
           "failed": check.failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["compared"] = check.numbers()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"the store's sources (src/repro) are not in {ROOT}", 2)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import bench, harness
    cell = bench.load_cell(args.workload)

    import jax
    bench.use_compile_cache(jax)
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        return fail(f"no TPU found (JAX reports {dev.platform!r}); the "
                    "benchmark measures the chip only", 3)
    if len(devs) < cell.chips:
        return fail(f"the cell asks for {cell.chips} chips, JAX sees "
                    f"{len(devs)}", 3)
    bench.peaks(dev.device_kind)

    run, check = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START,
                                  devices=devs[:cell.chips])
    out = result_line(cell, run, check, dev, cell.chips, bool(args.trace))
    print(f"window: {len(run.kinds)} batches, {run.ops} ops in "
          f"{run.window_s:.3f} s, {run.window_compiles} compiled in it; "
          f"set-up {run.setup_s:.3f} s; "
          f"{check.answers} answers and {check.rows_compared} full rows "
          "compared with the reference", file=sys.stderr)
    for line in check.examples:
        print(f"wrong: {line}", file=sys.stderr)
    for name, c in out["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
