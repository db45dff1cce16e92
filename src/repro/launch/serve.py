"""Serving drivers: dense LM decode, and the DPMR sparse serving engine.

Two modes behind one CLI:

  dense (default)   the original path: prefill + greedy decode of a model-
                    zoo architecture (`--arch`) on the host mesh.
  --sparse          the paper's face: a `repro.serve.DPMRServeEngine` keeps
                    the sharded parameter state resident on the mesh
                    (restored from a sparse checkpoint via `--ckpt`, or
                    optionally warm-trained in place with `--warm-steps`),
                    and `--clients` threads stream `file_sparse` /
                    `zipf_sparse`-shaped requests through the deadline-
                    coalesced micro-batcher + hot-feature cache. Prints
                    p50/p99 latency, sustained QPS, and the cache/batching
                    counters.

The modes are mutually exclusive and fail loudly when mixed: `--arch`
names a dense LM config and is rejected under `--sparse`, and `--sparse`
refuses a checkpoint directory whose manifest is not `kind=dpmr_sparse`.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b
  PYTHONPATH=src python -m repro.launch.train --sparse --steps 40 \
      --ckpt /tmp/ck                       # produce a sparse checkpoint
  PYTHONPATH=src python -m repro.launch.serve --sparse --ckpt /tmp/ck \
      --requests 256 --max-wait-ms 2
"""
from __future__ import annotations

import argparse
import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ParallelConfig, TrainConfig
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.runtime import compile_cache
from repro.train import serve, trainer

log = logging.getLogger("repro.serve")


def serve_sparse(args) -> dict:
    """Drive the sparse serving engine; returns the metrics snapshot."""
    from repro.api import DPMREngine
    from repro.configs.base import DPMRConfig
    from repro.data import get_source
    from repro.serve import BatchingConfig, DPMRServeEngine, HotCacheConfig

    mesh = make_host_mesh(args.mesh_data, args.mesh_model)
    if args.data_dir:
        source = get_source("file_sparse", directory=args.data_dir)
    else:
        source = get_source("zipf_sparse", batch_size=args.request_size,
                            num_batches=max(args.requests, 1),
                            num_features=args.features,
                            features_per_sample=16, seed=args.data_seed)
    probe = source.batch(0)
    k = int(probe["ids"].shape[1])
    cfg = DPMRConfig(num_features=args.features, max_features_per_sample=k,
                     distribution=args.strategy)

    batching = BatchingConfig(max_batch=args.max_batch,
                              max_wait_ms=args.max_wait_ms)
    hot = HotCacheConfig(max_hot=args.hot_max, threshold=args.hot_threshold,
                         window=args.hot_window,
                         refresh_every=args.hot_refresh_every) \
        if args.hot_cache else None

    if args.ckpt:
        srv = DPMRServeEngine.from_checkpoint(cfg, mesh, args.ckpt,
                                              batching=batching,
                                              hot_cache=hot)
        log.info("restored sparse state at step %d from %s",
                 int(srv.engine.state.step), args.ckpt)
    else:
        engine = DPMREngine(cfg, mesh)
        if args.warm_steps:
            engine.fit_sgd(source.iter_batches(), steps=args.warm_steps)
            log.info("warm-trained %d steps (no --ckpt given)",
                     args.warm_steps)
        else:
            log.warning("serving ZERO parameters (no --ckpt, no "
                        "--warm-steps): every probability is 0.5")
        srv = DPMRServeEngine(engine, batching=batching, hot_cache=hot)

    n = args.requests
    if source.num_batches is not None:
        n = min(n, source.num_batches)
    requests = [source.batch(i) for i in range(n)]
    results: list = [None] * n
    srv.metrics.reset_clock()
    t0 = time.time()

    def client(lo: int, hi: int) -> None:
        for i in range(lo, hi):
            results[i] = srv.submit(requests[i]["ids"],
                                    requests[i]["vals"])

    clients = max(1, args.clients)
    per = -(-n // clients)
    threads = [threading.Thread(target=client,
                                args=(c * per, min(n, (c + 1) * per)))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    probs = [np.asarray(f.result(timeout=120)) for f in results]
    wall = time.time() - t0
    srv.stop()

    m = srv.metrics_snapshot()
    print(f"[sparse] {n} requests x {requests[0]['ids'].shape[0]} samples "
          f"from {clients} clients in {wall:.2f}s "
          f"({n / max(wall, 1e-9):.1f} req/s)")
    print(f"  latency p50 {m.get('latency_p50_ms', float('nan')):.2f}ms "
          f"p99 {m.get('latency_p99_ms', float('nan')):.2f}ms; "
          f"flushes {m.get('flushes', 0)} "
          f"(full {m.get('flush_full', 0)} / deadline "
          f"{m.get('flush_deadline', 0)}); "
          f"compiled step fns {m['compiled_step_fns']}")
    if args.hot_cache:
        print(f"  hot cache: hit rate {m.get('hot_hit_rate', 0.0):.3f} "
              f"({m.get('cache_hits', 0)} hits / "
              f"{m.get('cache_misses', 0)} misses), "
              f"refreshes {m.get('cache_refreshes', 0)} "
              f"(stale {m.get('cache_stale_refreshes', 0)})")
    print(f"  first request -> {probs[0][:4]}")
    return m


def serve_dense(args) -> None:
    mesh = make_host_mesh(args.mesh_data, args.mesh_model)
    cfg = registry.smoke_config(args.arch) if args.smoke else \
        registry.get_spec(args.arch).cfg
    spec = registry.get_spec(args.arch)
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, TrainConfig(optimizer="sgd"),
                                   ParallelConfig(), jax.random.PRNGKey(0))
        params = state["params"]
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
            jnp.int32)}
        if cfg.family == "encdec":
            batch["frames"] = jnp.asarray(rng.normal(size=(
                args.batch, args.prompt_len, cfg.d_model)), jnp.float32)
        t0 = time.time()
        toks = serve.greedy_decode(spec, cfg, params, batch,
                                   args.decode_steps,
                                   ParallelConfig(seq_shard=False))
        dt = time.time() - t0
    print(f"decoded {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.decode_steps / dt:.1f} tok/s)")
    print(np.asarray(toks)[:2])


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="model zoo id (dense mode; rejected "
                                   "under --sparse)")
    # BooleanOptionalAction so --no-smoke can actually select the full
    # config (store_true with default=True could never be disabled)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family config (--no-smoke = full)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    # sparse serving mode
    ap.add_argument("--sparse", action="store_true",
                    help="serve the DPMR sparse face through "
                         "repro.serve.DPMRServeEngine")
    ap.add_argument("--ckpt", default="",
                    help="sparse: restore this sparse checkpoint "
                         "(manifest kind must be dpmr_sparse)")
    ap.add_argument("--features", type=int, default=1 << 14,
                    help="sparse: hashed feature-space size")
    ap.add_argument("--strategy", default="a2a",
                    help="sparse: distribution strategy name")
    ap.add_argument("--data-dir", default="",
                    help="sparse: serve requests shaped from a file_sparse "
                         "corpus instead of the synthetic zipf stream")
    ap.add_argument("--requests", type=int, default=128,
                    help="sparse: number of requests to drive")
    ap.add_argument("--request-size", type=int, default=4,
                    help="sparse: samples per request (zipf source)")
    ap.add_argument("--clients", type=int, default=8,
                    help="sparse: concurrent client threads")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="sparse: coalescer flush size (rows)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="sparse: coalescer deadline window")
    ap.add_argument("--hot-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="sparse: host-side Zipf-head parameter cache")
    ap.add_argument("--hot-max", type=int, default=256,
                    help="sparse: hot-cache slots")
    ap.add_argument("--hot-threshold", type=float, default=0.001,
                    help="sparse: min in-window frequency to cache")
    ap.add_argument("--hot-window", type=int, default=512,
                    help="sparse: sliding request window size")
    ap.add_argument("--hot-refresh-every", type=int, default=256,
                    help="sparse: staleness bound (lookups per mirror)")
    ap.add_argument("--warm-steps", type=int, default=0,
                    help="sparse: train this many steps in place when no "
                         "--ckpt is given (demo-quality parameters)")
    ap.add_argument("--data-seed", type=int, default=0)
    return ap


def main():
    logging.basicConfig(level=logging.INFO)
    ap = build_parser()
    args = ap.parse_args()
    compile_cache.enable()
    if args.sparse:
        if args.arch:
            # fail loudly instead of silently ignoring a dense config: the
            # two modes serve different state and share no flags
            ap.error(f"--arch {args.arch!r} is a dense LM config; the "
                     "sparse mode serves a DPMR checkpoint (--ckpt) — "
                     "pass exactly one of --arch / --sparse")
        serve_sparse(args)
        return
    if not args.arch:
        ap.error("--arch is required (or pass --sparse)")
    serve_dense(args)


if __name__ == "__main__":
    main()
