import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any jax import: jax locks the device count
at first init, and the production meshes need 512 placeholder host devices.
(Smoke tests and benchmarks never import this module, so they see 1 device.)

Per cell this prints/records:
  - compiled.memory_analysis()  (bytes per device: proves it fits)
  - compiled.cost_analysis()    (HLO FLOPs / bytes for the roofline terms)
  - the collective schedule (op kind, dtype, shape, participant count)
    parsed from the optimized HLO — cost_analysis has no collective bytes.

Usage:
  python -m repro.launch.dryrun --cell granite-8b:train_4k:single   # one cell
  python -m repro.launch.dryrun --all --out results/dryrun          # sweep
The sweep spawns one subprocess per cell (compile isolation + memory reclaim
on a 1-core host); each cell appends <out>/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import re
import subprocess
import sys
import time


def _collectives_from_hlo(hlo: str):
    """Parse collective ops from optimized HLO text.

    Returns a list of {op, dtype, shape, elems, bytes, groups, group_size}.
    """
    ops = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")
    dsize = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
             "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}
    out = []
    # e.g.:  %ag = bf16[16,1024,512]{...} all-gather(...), replica_groups=...
    pat = re.compile(
        r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^a-z]*\s*"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(")
    gpat = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
    gpat2 = re.compile(r"replica_groups=\{\{([^}]*)\}")
    for line in hlo.splitlines():
        if not any(o in line for o in ops):
            continue
        m = pat.search(line)
        if not m:
            continue
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        if "-done" in line:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        gsize = None
        g = gpat.search(line)
        if g:
            gsize = int(g.group(2))
        else:
            g2 = gpat2.search(line)
            if g2:
                gsize = len(g2.group(1).split(","))
        out.append({
            "op": kind, "dtype": dt, "elems": elems,
            "bytes": elems * dsize.get(dt, 4), "group_size": gsize,
        })
    return out


def run_strategy_wire(global_batch: int = 1 << 24, k: int = 64,
                      feature_space: int = 1 << 30) -> list:
    """Two-tier wire report for every registered distribution strategy on
    the production mesh geometries (analytic — no compilation).

    Per (mesh, strategy): bytes/device/step on the fast tier (ICI, inner
    axes) and across DCN (the `pod` outer axis), from each strategy's own
    `bytes_per_device` model at the paper's full-batch regime, plus the
    autotuner's wire-cost ranking (each tier's bytes charged at that
    tier's bandwidth, `repro.api.autotune`) — the per-mesh winner, i.e.
    what `DPMRConfig.distribution="auto"` would pick, is marked `*`. The
    multi rows are where the hierarchical family earns its keep: its DCN
    bytes are the table block (or a sparsified fraction of it for
    `hier_a2a+topk`), not the shuffled request volume.
    """
    from repro.api import autotune
    from repro.api.strategies import StrategyContext
    from repro.configs.base import DPMRConfig
    from repro.core import dpmr

    cfg = DPMRConfig(num_features=feature_space, max_features_per_sample=k)
    rows = []
    # geometry of make_production_mesh: single (16,16); multi (2,16,16)
    for mesh_kind, p, po in (("single", 256, 1), ("multi", 512, 2)):
        cap = dpmr.capacity_for_shards(cfg, global_batch // p, p)
        ctx = StrategyContext(axes=(), num_shards=p,
                              block_size=-(-feature_space // p),
                              capacity=cap, outer_shards=po,
                              topk_frac=cfg.topk_frac)
        ranked = autotune.score_strategies(ctx)
        winner = ranked[0].name
        for rank, s in enumerate(ranked, start=1):
            rows.append({"mesh": mesh_kind, "strategy": s.name,
                         "shards": p, "pods": po, "capacity": cap,
                         "inner_bytes": int(s.wire.inner),
                         "outer_bytes": int(s.wire.outer),
                         "total_bytes": int(s.wire.total),
                         "cost_us": s.cost_s * 1e6, "rank": rank,
                         "lossy": s.lossy, "chosen": s.name == winner})
    print(f"{'mesh':>7s} {'strategy':>18s} {'ICI B/dev':>12s} "
          f"{'DCN B/dev':>12s} {'total':>12s} {'cost us':>9s} "
          f"{'rank':>4s}")
    for r in rows:
        mark = " *" if r["chosen"] else ("  " if not r["lossy"] else " ~")
        print(f"{r['mesh']:>7s} {r['strategy']:>18s} "
              f"{r['inner_bytes']:>12.3e} {r['outer_bytes']:>12.3e} "
              f"{r['total_bytes']:>12.3e} {r['cost_us']:>9.1f} "
              f"{r['rank']:>4d}{mark}")
    print("  * = autotuner's pick (distribution=\"auto\"); "
          "~ = lossy (error-feedback carry)")
    return rows


def _probe_config(cfg, n: int):
    """Reduced-DEPTH same-width config with n 'units' + the real unit count.

    A unit is whatever repeats: a layer (dense/moe/vlm), an enc+dec layer
    pair (whisper), a mamba group + shared block (zamba), an mLSTM+sLSTM
    pair (xlstm). Costs are affine in units, so two probes extrapolate
    exactly (attention/SSD inner scans are python-unrolled via
    models.layers.PROBE_UNROLL so nothing hides in a while body).
    """
    import dataclasses

    if cfg.family == "encdec":
        return dataclasses.replace(cfg, num_layers=n, encoder_layers=n), \
            cfg.num_layers
    if cfg.family == "hybrid":
        every = max(cfg.attn_every, 1)
        return dataclasses.replace(cfg, num_layers=n * every), \
            cfg.num_layers // every
    if cfg.family == "ssm":
        pair = max(cfg.slstm_every, 1)
        return dataclasses.replace(cfg, num_layers=n * pair), \
            cfg.num_layers // pair
    return dataclasses.replace(cfg, num_layers=n), cfg.num_layers


def _parse_overrides(s: str) -> dict:
    """'attn_mode=cp,microbatches=4' -> dict with typed values."""
    out = {}
    if not s:
        return out
    for kv in s.split(","):
        k, v = kv.split("=")
        if v in ("True", "False"):
            out[k] = v == "True"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                out[k] = v
    return out


def run_probe(arch: str, shape_name: str, overrides: str = "") -> dict:
    """Unrolled 1-unit and 2-unit cost probes on the single-pod mesh."""
    import dataclasses

    import jax

    from repro.configs import SHAPES, TrainConfig
    from repro.configs.base import ParallelConfig
    from repro.launch.mesh import make_production_mesh
    from repro.models import layers as layers_mod
    from repro.models import registry
    from repro.sharding import tree_sds, tree_shardings
    from repro.train import serve, trainer

    spec0 = registry.get_spec(arch)
    shape = SHAPES[shape_name]
    if shape_name not in spec0.supported_shapes:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": spec0.skip_reason}

    layers_mod.PROBE_UNROLL = True
    mesh = make_production_mesh(multi_pod=False)
    tc = TrainConfig()
    # probe at MICROBATCH size: the real step is `micro` sequential passes,
    # so step cost = micro x extrapolated probe cost (exact for both the
    # batch-linear activation collectives and the per-pass param gathers)
    ovr = _parse_overrides(overrides)
    batch_dm = ovr.pop("batch_dm", False)
    micro = ovr.pop("microbatches", None) or (
        _parallel_for(arch, shape_name, "single").microbatches
        if shape.kind == "train" else 1)
    if shape.kind == "train" and shape.global_batch % micro == 0:
        shape = dataclasses.replace(
            shape, global_batch=shape.global_batch // micro)
    out = {"arch": arch, "shape": shape_name, "status": "ok",
           "kind": shape.kind, "microbatches": micro,
           "overrides": overrides}
    rules = None
    repl_vocab = ovr.pop("replicate_vocab", False)
    if batch_dm or repl_vocab:
        from repro import sharding as shd
        rules = dict(shd.DEFAULT_RULES)
        if batch_dm:
            rules["batch"] = ("pod", "data", "model")
        if repl_vocab:
            rules["vocab"] = ()
    try:
        with jax.set_mesh(mesh):
            for n in (1, 2):
                cfg, units = _probe_config(spec0.cfg, n)
                spec = dataclasses.replace(spec0, cfg=cfg)
                parallel = ParallelConfig(microbatches=1, remat="full",
                                          scan_layers=False, **ovr)
                if shape.kind == "train":
                    sdefs = trainer.state_defs(spec, cfg, tc, parallel)
                    bdefs = registry.batch_defs(spec, shape)
                    step = trainer.make_train_step(spec, cfg, tc, parallel,
                                                   mesh)
                    fn = jax.jit(step, in_shardings=(
                        tree_shardings(sdefs, mesh, rules),
                        tree_shardings(bdefs, mesh, rules)))
                    args = (tree_sds(sdefs), tree_sds(bdefs))
                elif shape.kind == "prefill":
                    pdefs = spec.defs(cfg)
                    bdefs = registry.batch_defs(spec, shape)
                    step = serve.make_prefill_step(spec, cfg, parallel)
                    fn = jax.jit(step, in_shardings=(
                        tree_shardings(pdefs, mesh),
                        tree_shardings(bdefs, mesh)))
                    args = (tree_sds(pdefs), tree_sds(bdefs))
                else:
                    pdefs = spec.defs(cfg)
                    bdefs = registry.batch_defs(spec, shape)

                    def step(params, cache, tokens):
                        return spec.decode_step(params, cache, tokens, cfg,
                                                unroll=True)

                    cache_sh = tree_shardings(bdefs["cache"], mesh)
                    fn = jax.jit(step, in_shardings=(
                        tree_shardings(pdefs, mesh),
                        cache_sh,
                        tree_shardings(bdefs["tokens"], mesh)),
                        # keep the returned cache in-place (production would
                        # also donate); otherwise GSPMD remats it under a
                        # fresh sharding = phantom collectives
                        out_shardings=(None, cache_sh))
                    args = (tree_sds(pdefs), tree_sds(bdefs["cache"]),
                            tree_sds(bdefs["tokens"]))
                lowered = fn.lower(*args)
                compiled = lowered.compile()
                cost = compiled.cost_analysis()
                colls = _collectives_from_hlo(compiled.as_text())
                agg = {}
                for c in colls:
                    a = agg.setdefault(c["op"], {"count": 0, "bytes": 0})
                    a["count"] += 1
                    a["bytes"] += c["bytes"]
                out[f"probe{n}"] = {
                    "flops": float(cost.get("flops", -1)),
                    "bytes_accessed": float(cost.get("bytes accessed", -1)),
                    "transcendentals": float(cost.get("transcendentals", 0)),
                    "collective_summary": agg,
                }
                out["units"] = units
    finally:
        layers_mod.PROBE_UNROLL = False
    print(json.dumps(out, indent=1))
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             collect_hlo: bool = True, overrides: str = "") -> dict:
    import dataclasses

    import jax

    from repro.configs import SHAPES, TrainConfig
    from repro.configs.base import ParallelConfig
    from repro.launch.mesh import make_production_mesh
    from repro.models import registry
    from repro.sharding import tree_sds, tree_shardings
    from repro.train import trainer

    t0 = time.time()
    spec = registry.get_spec(arch)
    cfg = spec.cfg
    shape = SHAPES[shape_name]
    if shape_name not in spec.supported_shapes:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": spec.skip_reason}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    parallel = _parallel_for(arch, shape_name, mesh_kind)
    if overrides:
        parallel = dataclasses.replace(parallel, **_parse_overrides(overrides))
    tc = TrainConfig()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            sdefs = trainer.state_defs(spec, cfg, tc, parallel)
            bdefs = registry.batch_defs(spec, shape)
            step = trainer.make_train_step(spec, cfg, tc, parallel, mesh)
            in_sh = (tree_shardings(sdefs, mesh), tree_shardings(bdefs, mesh))
            args = (tree_sds(sdefs), tree_sds(bdefs))
            fn = jax.jit(step, in_shardings=in_sh)
        elif shape.kind == "prefill":
            pdefs = spec.defs(cfg)
            bdefs = registry.batch_defs(spec, shape)
            from repro.train import serve
            step = serve.make_prefill_step(spec, cfg, parallel)
            in_sh = (tree_shardings(pdefs, mesh), tree_shardings(bdefs, mesh))
            args = (tree_sds(pdefs), tree_sds(bdefs))
            fn = jax.jit(step, in_shardings=in_sh)
        else:  # decode
            pdefs = spec.defs(cfg)
            bdefs = registry.batch_defs(spec, shape)
            from repro.train import serve
            step = serve.make_decode_step(spec, cfg)
            cache_sh = tree_shardings(bdefs["cache"], mesh)
            in_sh = (tree_shardings(pdefs, mesh), cache_sh,
                     tree_shardings(bdefs["tokens"], mesh))
            args = (tree_sds(pdefs), tree_sds(bdefs["cache"]),
                    tree_sds(bdefs["tokens"]))
            fn = jax.jit(step, in_shardings=in_sh,
                         out_shardings=(None, cache_sh))

        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        rec = {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "status": "ok",
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "memory_analysis": _mem_dict(mem),
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
            "cost_keys": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))
                          and abs(float(v)) < 1e30},
        }
        if collect_hlo:
            hlo = compiled.as_text()
            colls = _collectives_from_hlo(hlo)
            agg = {}
            for c in colls:
                k = c["op"]
                a = agg.setdefault(k, {"count": 0, "bytes": 0})
                a["count"] += 1
                a["bytes"] += c["bytes"]
            rec["collectives"] = colls
            rec["collective_summary"] = agg
            del hlo
        print(json.dumps({k: v for k, v in rec.items()
                          if k != "collectives"}, indent=1))
        return rec


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for k in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _parallel_for(arch: str, shape_name: str, mesh_kind: str):
    """Per-cell parallel config: microbatching keeps activations in HBM."""
    from repro.configs.base import ParallelConfig

    micro = {
        ("llama3-405b", "train_4k"): 16,
        ("mixtral-8x22b", "train_4k"): 8,
        ("chameleon-34b", "train_4k"): 4,
        ("granite-34b", "train_4k"): 4,
        ("phi3.5-moe-42b-a6.6b", "train_4k"): 4,
        ("granite-8b", "train_4k"): 2,
        ("yi-6b", "train_4k"): 2,
        ("zamba2-2.7b", "train_4k"): 8,   # no SP inside SSM blocks: rely on
        ("xlstm-125m", "train_4k"): 2,    # grad accumulation for activations
        ("whisper-small", "train_4k"): 2,
    }.get((arch, shape_name), 1)
    accum = "bfloat16" if arch in ("llama3-405b", "mixtral-8x22b") else \
        "float32"
    return ParallelConfig(microbatches=micro, remat="full",
                          accum_dtype=accum)


CELLS_MESHES = ("single", "multi")


def all_cells():
    from repro.configs import ARCH_IDS, SHAPES
    from repro.models import registry

    cells = []
    for arch in ARCH_IDS:
        spec = registry.get_spec(arch)
        for shape in SHAPES:
            for mk in CELLS_MESHES:
                cells.append((arch, shape, mk,
                              shape in spec.supported_shapes))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", help="arch:shape:mesh  (runs in-process)")
    ap.add_argument("--strategies", action="store_true",
                    help="print the two-tier (ICI/DCN) wire model of every "
                         "registered distribution strategy on the "
                         "production mesh geometries")
    ap.add_argument("--probe", action="store_true",
                    help="run the 1/2-unit unrolled cost probes instead")
    ap.add_argument("--pconf", default="",
                    help="ParallelConfig overrides, e.g. attn_mode=cp")
    ap.add_argument("--tag", default="",
                    help="suffix for the probe result filename")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", default="both", choices=("single", "multi",
                                                       "both"))
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have results")
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()

    if args.strategies:
        rows = run_strategy_wire()
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, "strategy_wire.json"),
                      "w") as f:
                json.dump(rows, f, indent=1)
        return

    if args.cell:
        parts = args.cell.split(":")
        arch, shape = parts[0], parts[1]
        if args.probe:
            rec = run_probe(arch, shape, overrides=args.pconf)
            suffix = "probe" + (f"_{args.tag}" if args.tag else "")
        else:
            mk = parts[2]
            rec = run_cell(arch, shape, mk, collect_hlo=not args.no_hlo,
                           overrides=args.pconf)
            suffix = mk + (f"_{args.tag}" if args.tag else "")
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            safe = f"{arch}__{shape}__{suffix}".replace("/", "_")
            with open(os.path.join(args.out, safe + ".json"), "w") as f:
                json.dump(rec, f)
        return

    assert args.all
    os.makedirs(args.out, exist_ok=True)
    if args.probe:
        seen = set()
        for arch, shape, _, supported in all_cells():
            if (arch, shape) in seen:
                continue
            seen.add((arch, shape))
            safe = f"{arch}__{shape}__probe".replace("/", "_")
            path = os.path.join(args.out, safe + ".json")
            if os.path.exists(path) and not args.force:
                print(f"[skip existing] {safe}")
                continue
            if not supported:
                from repro.models import registry
                spec = registry.get_spec(arch)
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape,
                               "status": "skipped",
                               "reason": spec.skip_reason}, f)
                continue
            print(f"[probe] {safe}", flush=True)
            t0 = time.time()
            proc = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun", "--cell",
                 f"{arch}:{shape}", "--probe", "--out", args.out],
                capture_output=True, text=True, timeout=args.timeout,
                env={**os.environ, "PYTHONPATH": "src"})
            if proc.returncode != 0:
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape,
                               "status": "error",
                               "stderr": proc.stderr[-4000:]}, f)
                print(f"[FAIL {time.time()-t0:.0f}s] {safe}\n"
                      f"{proc.stderr[-1500:]}")
            else:
                print(f"[ok {time.time()-t0:.0f}s] {safe}")
        return
    meshes = CELLS_MESHES if args.mesh == "both" else (args.mesh,)
    for arch, shape, mk, supported in all_cells():
        if mk not in meshes:
            continue
        safe = f"{arch}__{shape}__{mk}".replace("/", "_")
        path = os.path.join(args.out, safe + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[skip existing] {safe}")
            continue
        if not supported:
            from repro.models import registry
            spec = registry.get_spec(arch)
            with open(path, "w") as f:
                json.dump({"arch": arch, "shape": shape, "mesh": mk,
                           "status": "skipped",
                           "reason": spec.skip_reason}, f)
            print(f"[skipped-by-design] {safe}")
            continue
        print(f"[run] {safe}", flush=True)
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--cell",
             f"{arch}:{shape}:{mk}", "--out", args.out]
            + (["--no-hlo"] if args.no_hlo else []),
            capture_output=True, text=True, timeout=args.timeout,
            env={**os.environ, "PYTHONPATH": "src"},
        )
        if proc.returncode != 0:
            with open(path, "w") as f:
                json.dump({"arch": arch, "shape": shape, "mesh": mk,
                           "status": "error",
                           "stderr": proc.stderr[-4000:]}, f)
            print(f"[FAIL {time.time()-t0:.0f}s] {safe}\n{proc.stderr[-2000:]}")
        else:
            print(f"[ok {time.time()-t0:.0f}s] {safe}")


if __name__ == "__main__":
    main()
