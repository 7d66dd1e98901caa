#!/usr/bin/env python3
"""The control and the seed sweep behind ``correct``'s limits.

    python3 perfbench/control.py --workload ycsb-a --seconds 20 \
        --seeds 11,12,13 --control-seeds 21,22,23

Runs the cell as the benchmark does, in one process, once per program
seed and once per control seed, and prints one JSON line per run with
the numbers compared and whether the run came out correct.  The control
is the store with one guarantee broken: ``LaggedPuts`` acknowledges a
put before applying it and applies it with the next put (write-behind),
so a get can read the version before the latest.  The control has to
come out not correct.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time


class LaggedPuts:
    """Write-behind: each put is held and applied with the next one."""

    def __init__(self, db):
        self.db = db
        self.held = None

    def put(self, keys, vals):
        held, self.held = self.held, (keys, vals)
        if held is not None:
            self.db.put(*held)

    def __getattr__(self, name):
        return getattr(self.db, name)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    import jax
    from perfbench import bench, harness
    bench.use_compile_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 3
    cell = bench.load_cell(args.workload)
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        print(f"control: the cell asks for {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    todo = [("program", int(s)) for s in args.seeds.split(",") if s] + \
        [("control", int(s)) for s in args.control_seeds.split(",") if s]
    for kind, seed in todo:
        db = harness.build_store(cell.config, devices)
        store = LaggedPuts(db) if kind == "control" else db
        run, check = harness.run_cell(cell, seed, args.seconds, False,
                                      time.perf_counter(), store=store)
        print(json.dumps({"workload": cell.name, "run": kind, "seed": seed,
                          "correct": check.correct,
                          "answers": check.answers,
                          "rows": check.rows_compared,
                          "batches": len(run.kinds),
                          "compared": check.numbers()}), flush=True)
        del db, store, run, check
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
