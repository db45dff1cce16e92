"""Benchmark harness entrypoint: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Wall-clock microbenchmarks are
measured on this host's CPU (meaningful relatively, not as TPU numbers);
derived columns carry the paper-relevant quantity (speedup linearity,
convergence F, overflow, byte ratios). Roofline terms come from the dry-run
artifacts if present (results/probes + results/dryrun).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time_us(fn, *args, iters: int = 5, warmup: int = 2):
    for _ in range(warmup):
        r = fn(*args)
        jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
        jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_table1_stage_scaling():
    """Paper Table 1: per-stage scaling with shard count."""
    from benchmarks import stage_scaling

    rows = stage_scaling.run()
    p0 = rows[0]["shards"]
    worst = min(r["speedup_vs_first"] / (r["shards"] / p0) for r in rows)
    print(f"table1_stage_scaling,0,linearity={worst:.3f}")
    return rows


def bench_fig1_convergence():
    """Paper Fig 1: P/R/F convergence over iterations."""
    from benchmarks import convergence

    t0 = time.perf_counter()
    hist = convergence.run(iterations=8)
    dt = (time.perf_counter() - t0) * 1e6
    print(f"fig1_convergence,{dt/8:.0f},f_avg_final={hist[-1]['f_avg']:.3f}")
    return hist


def bench_sec4_hot_sharding():
    from benchmarks import hot_sharding

    rows = hot_sharding.run()
    base = rows[0]["imbalance"]
    best = min(r["imbalance"] for r in rows[1:])
    print(f"sec4_hot_sharding,0,owner_imbalance_{base:.2f}->{best:.2f}")
    return rows


def bench_a2a_vs_allgather():
    from benchmarks import a2a_vs_allgather

    rows = a2a_vs_allgather.run()
    print(f"a2a_vs_allgather,0,ratio_at_2^33={rows[-1]['ratio']:.0f}x")
    return rows


def bench_dpmr_step():
    """Wall time of one DPMR SGD step (CPU, relative use only)."""
    from repro.api import DPMREngine, get_source
    from repro.configs.base import DPMRConfig
    from repro.launch.mesh import make_host_mesh

    src = get_source("zipf_sparse", batch_size=1024, num_features=1 << 16,
                     features_per_sample=32)
    cfg = DPMRConfig(num_features=1 << 16, max_features_per_sample=32)
    engine = DPMREngine(cfg, make_host_mesh(1, 1))
    fns = engine.step_fns(1024)
    b = engine.put_batch(src.batch(0))

    def step():
        # train_step donates the state; thread the returned one so every
        # timed call consumes a live buffer (engine.state stays current)
        engine.state, _ = fns.train_step(engine.state, b)
    us = _time_us(step)
    print(f"dpmr_sgd_step_b1024,{us:.0f},tokens_per_s="
          f"{1024 / (us / 1e6):.0f}")


def bench_input_pipeline():
    """Loader throughput + prefetch overlap (see benchmarks/input_pipeline)."""
    from benchmarks import input_pipeline

    res = input_pipeline.run(quick=True, write_json=False)
    print(f"input_pipeline,0,overlap_speedup="
          f"{res['results']['fit_sgd']['speedup']:.2f}x")


def bench_shard_ownership():
    """Chunk-ownership locality: files opened per host vs stride baseline."""
    from benchmarks import shard_ownership

    res = shard_ownership.run(num_chunks=8, batches_per_chunk=4,
                              batch_size=64, hosts=(1, 4),
                              write_json=False)
    row = res["results"]["sweep"][-1]
    print(f"shard_ownership,0,opens_per_host="
          f"{row['stride_baseline']['max_files_opened']}->"
          f"{row['ownership']['max_files_opened']}@H={row['hosts']}")


def bench_strategy_overlap():
    """Top-k wire reduction + overlap bit-identity (see strategy_overlap)."""
    from benchmarks import strategy_overlap

    rows = strategy_overlap.topk_wire_sweep()
    at_default = next(r for r in rows if r["topk_frac"] == 0.25)
    ov = strategy_overlap.overlap_rows(steps=5)
    print(f"strategy_overlap,0,topk_total_wire_x"
          f"{at_default['total_reduction_x']:.2f}"
          f"_overlap_bit_identical={ov['bit_identical']}")


def bench_kernels():
    """Interpret-mode kernel calls vs jnp oracle (correct-by-construction
    check is in tests; here: relative CPU wall time)."""
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(size=(512, 64)).astype(np.float32))
    theta = jnp.asarray(rng.normal(size=(512, 64)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, size=(512,)).astype(np.int32))
    us = _time_us(lambda: ops.sigmoid_grad(vals, theta, y, impl="jnp"))
    print(f"kernel_sigmoid_grad_jnp,{us:.0f},B=512xK=64")

    ids = jnp.asarray(np.sort(rng.integers(0, 997, size=4096))
                      .astype(np.int32))
    g = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    us = _time_us(lambda: ops.segment_sum_sorted(ids, g, impl="jnp"))
    print(f"kernel_segment_sum_jnp,{us:.0f},N=4096")

    q = jnp.asarray(rng.normal(size=(2, 256, 4, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 32)).astype(np.float32))
    us = _time_us(lambda: ops.flash_attention(q, k, v, impl="jnp"))
    print(f"kernel_flash_attention_jnp,{us:.0f},S=256_GQA4:2")


def bench_train_step():
    """Smoke-scale LM train step wall time (CPU)."""
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.data import get_source
    from repro.launch.mesh import make_host_mesh
    from repro.models import registry
    from repro.train import trainer

    mesh = make_host_mesh(1, 1)
    cfg = registry.smoke_config("granite-8b")
    spec = registry.get_spec("granite-8b")
    tc = TrainConfig()
    pc = ParallelConfig()
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, tc, pc, jax.random.PRNGKey(0))
        step = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))
        src = get_source("lm_markov", vocab_size=cfg.vocab_size, seq_len=64,
                         batch_size=8)
        b = jax.tree.map(jnp.asarray, src.batch(0))
        us = _time_us(lambda: step(state, b))
    toks = 8 * 64
    print(f"lm_train_step_smoke,{us:.0f},tokens_per_s={toks/(us/1e6):.0f}")


def bench_roofline():
    """Roofline table from the dry-run artifacts (if present)."""
    import os

    if not (os.path.isdir("results/probes")
            and os.path.isdir("results/dryrun")):
        print("roofline,0,skipped_no_dryrun_artifacts")
        return
    from benchmarks import roofline

    rows = roofline.analyze()
    if not rows:
        print("roofline,0,no_probe_results_yet")
        return
    worst = min(rows, key=lambda r: r["roofline_fraction"])
    print(f"roofline_cells,{len(rows)},worst={worst['arch']}:"
          f"{worst['shape']}@{100*worst['roofline_fraction']:.0f}%")


def main() -> None:
    print("name,us_per_call,derived")
    bench_table1_stage_scaling()
    bench_fig1_convergence()
    bench_sec4_hot_sharding()
    bench_a2a_vs_allgather()
    bench_dpmr_step()
    bench_input_pipeline()
    bench_shard_ownership()
    bench_strategy_overlap()
    bench_kernels()
    bench_train_step()
    bench_roofline()


if __name__ == "__main__":
    main()
