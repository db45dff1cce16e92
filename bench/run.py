#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload train.zipf27 --seed 7 --seconds 10 \
        --trace 0

The cell is an entry of `workloads` in BENCHMARK.json. It names a
configuration (bench/configs/<config>.json) and a traffic mix
(bench/traffic/<traffic>.json); the mix names its driver
(bench/drivers/<driver>.py), and the cell's limits on the numbers that
decide `correct` are in bench/limits/<cell>.json. Each per-layer metric is
read by bench/metrics/<metric>.py. Nothing here knows a cell by name.

The run makes its inputs from --seed, warms up (that is `setup_s`),
measures for --seconds, then checks what the measured path produced
against the plain reference. The last line of standard output is one JSON
object: `correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end
metrics with --trace 0, its per-layer metrics with --trace 1), `device`,
with --trace 1 `breakdown`, and last `checks`: each compared number with
its limit. The same numbers end standard error.

It exits 2 and prints no result where JAX finds no TPU or fewer chips than
the cell asks for. JAX's compilation cache is `.jax_cache/` in this
checkout; traces go to `.bench_trace/`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
CACHE = os.path.join(ROOT, ".jax_cache")
TRACE = os.path.join(ROOT, ".bench_trace")


def cell_spec(name: str) -> tuple[dict, dict]:
    """The cell's entry of BENCHMARK.json, and the whole file."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["name"] == name:
            return w, bench
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(cell: str, entries: list[dict]) -> list[dict]:
    return [m for m in entries if cell in m.get("workloads", [cell])]


class Tracer:
    """The profiler around the measured window, reduced afterwards."""

    def __init__(self, on: bool):
        self.on = on

    def start(self):
        if self.on:
            import jax

            shutil.rmtree(TRACE, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE, profiler_options=opts)

    def stop(self):
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def reduce(self, hlo_texts=()) -> dict:
        from bench import trace_reduce

        return trace_reduce.reduce(trace_reduce.find_xplane(TRACE),
                                   hlo_texts)


def run_cell(cell: dict, bench: dict, seed: int, seconds: float,
             trace: bool, *, setup_start: float | None = None,
             config: dict | None = None, traffic: dict | None = None) -> dict:
    """Run the cell once and return the result line as a dict. `config`
    and `traffic` replace the cell's files (the tests run it small)."""
    from bench import common, peaks

    config = config or common.load_json("configs", f"{cell['config']}.json")
    traffic = traffic or common.load_json("traffic",
                                          f"{cell['traffic']}.json")
    driver = common.load_module("drivers", traffic["driver"])
    tracer = Tracer(trace)
    compiles = common.CompileCounter()
    marks = {}
    t_setup = T_START if setup_start is None else setup_start

    def window_open():
        marks["setup_s"] = time.perf_counter() - t_setup
        tracer.start()
        compiles.on = True

    def window_closed():
        compiles.on = False
        tracer.stop()

    phases = {"start": time.perf_counter() - t_setup}
    ctx = {"config": config, "traffic": traffic, "seed": seed,
           "seconds": seconds, "trace": trace, "chips": cell["chips"],
           "limits": common.load_json("limits", f"{cell['name']}.json"),
           "window_open": window_open, "window_closed": window_closed,
           "setup_phases": phases}
    out = driver.run(ctx)
    print("setup phases (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items()), file=sys.stderr,
        flush=True)
    print(f"compilations in the window: {compiles.n}", file=sys.stderr,
          flush=True)

    device = common.device_info()
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    found = dict(out["end_to_end"], setup_s=marks["setup_s"],
                 peak_hbm_gib=out["memory_peak_bytes"] / 2**30)
    line = {"correct": common.judge(out["checks"]),
            "attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        reduced = tracer.reduce(out["record"].get("hlo", ()))
        record = dict(out["record"], trace=reduced,
                      peaks=peaks.peaks(device["kind"]))
        found = {}
        for m in metrics_for(cell["name"], bench["per_layer"]):
            v = common.load_module("metrics", m["name"]).read(record)
            if v is not None:
                found[m["name"]] = v
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        entries = bench["per_layer"]
        line["breakdown"] = {"device_ops": reduced["top_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    else:
        entries = bench["end_to_end"]
    line["metrics"] = {m["name"]: {"value": found[m["name"]],
                                   "unit": m["unit"]}
                       for m in metrics_for(cell["name"], entries)
                       if m["name"] in found}
    line["device"] = device
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out["checks"].items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, bench = cell_spec(args.workload)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"refusing to run: {args.workload} needs {cell['chips']} "
              f"TPU chip(s), JAX sees {len(devs)} {devs[0].platform} "
              "device(s)", file=sys.stderr)
        return 2
    line = run_cell(cell, bench, args.seed, args.seconds, bool(args.trace))
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
