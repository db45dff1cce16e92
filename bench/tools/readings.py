#!/usr/bin/env python3
"""The readings that the limits in bench/limits/<cell>.json are set from.

    python3 bench/tools/readings.py --workload train.zipf27 \
        --seeds 101-112 --seconds 2 --faults half_batch

In one process (the chip is held once, and the compilation cache warms
once), for each seed: a whole run of the cell through its driver, at the
cell's own size and load with a short window, giving the program's
compared numbers and, beside them, the control's (the reference computed
in bfloat16, on the same inputs); then one run per named fault planted
under the program (bench/faults.py). One JSON line per run goes to
standard output. The benchmark's own runs never do this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112,7")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", default="", help="comma-separated")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    from bench import common, faults, run

    cell, _ = run.cell_spec(args.workload)
    config = common.load_json("configs", f"{cell['config']}.json")
    traffic = common.load_json("traffic", f"{cell['traffic']}.json")
    driver = common.load_module("drivers", traffic["driver"])
    limits = common.load_json("limits", f"{cell['name']}.json")

    def once(seed, fault=None):
        ctx = {"config": config, "traffic": traffic, "seed": seed,
               "seconds": args.seconds, "trace": False,
               "chips": cell["chips"], "limits": limits,
               "control": fault is None,
               "window_open": lambda: None, "window_closed": lambda: None}
        t = time.perf_counter()
        if fault is None:
            out = driver.run(ctx)
        else:
            with faults.planted(fault):
                out = driver.run(ctx)
        gc.collect()
        line = {"workload": cell["name"], "seed": seed, "fault": fault,
                "program": {k: v for k, (v, _) in out["checks"].items()},
                "control": out["control"], "attempted": out["attempted"],
                "failed": out["failed"],
                "seconds": time.perf_counter() - t}
        print(json.dumps(line), flush=True)

    wanted = [f for f in args.faults.split(",") if f]
    for i, s in enumerate(seeds(args.seeds)):
        once(s)
        if i < args.fault_seeds:
            for f in wanted:
                once(s, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
