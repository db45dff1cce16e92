#!/usr/bin/env python
"""Chip smoke: train and serve the 2^27-feature DPMR model on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the a2a exchange on four chips
    python chip_smoke.py --seed 3    # another corpus (default seed 0)

The model is the repository's "~100M model" (`examples/train_dpmr_100m.py
--log2-features 27`): sparse logistic regression over 2^27 = 134,217,728
features, K = 64 ids per sample, global batch 4096, adagrad, the `a2a`
exchange. Data is the seeded `zipf_sparse` corpus read through a
`ShardedLoader`; the hot set comes from `hot_ids_from_corpus`. Everything
runs in this one process, through `DPMREngine` and `DPMRServeEngine`.

One chip (the default):
  train      20 `fit_sgd` steps, `kernel_impl="xla"`; loss and overflow of
             every step, the first step's time (compile + run), the later
             steps' time per step in the engine's `dpmr.dispatch` and
             `dpmr.metrics_sync` spans, and the peak HBM (informational,
             not metrics)
  reference  the same steps through `repro.core.reference` (one dense
             float32 table, no routing); losses and parameters must agree
  pallas     the same steps with `kernel_impl="pallas"` (the sigmoid_grad
             and owner_accumulate / segment_sum_sorted kernels, compiled);
             must agree with the XLA run
  serve      async save, `DPMRServeEngine.from_checkpoint`, requests from
             two client threads, most of them one-row requests cut to the
             head features so that the hot cache answers them; every answer
             must equal `engine.predict` bit for bit

`--chips 4` runs only the exchange: the training phase on a 1x4 mesh
(P = 4, the a2a shuffle carries real traffic), the same steps on a
one-chip mesh, and the reference; all three must agree.

A run that finds no TPU exits 1 before any phase and prints no result.
A failed phase exits 1. A passing run's last line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

LOG2_FEATURES = 27
K = 64
BATCH = 4096
STEPS = 20
REQUESTS = 48                 # mixed-size requests, split over two threads
REQUEST_ROWS = (1, 3, 8, 16)  # their sizes, cycled
HOT_REQUESTS = 320            # one-row requests cut to the head features
HEAD = 64                     # head features those rows are cut to
# float32 summation order differs between the engine and the reference
# (and between the XLA and Pallas combiners); nothing else may differ
RTOL, ATOL = 1e-5, 1e-4


class SmokeFailure(Exception):
    pass


def device_line() -> dict:
    """The device as JAX reports it; raises SmokeFailure off a TPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"platform={d.platform} device_kind={d.device_kind} "
          f"device_count={len(devs)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's platform is {d.platform!r}; this smoke runs "
            "only on a TPU and never falls back")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def make_cfg(kernel_impl: str = "xla"):
    from repro.configs.base import DPMRConfig

    return DPMRConfig(num_features=1 << LOG2_FEATURES,
                      max_features_per_sample=K, learning_rate=2.0,
                      max_hot=512, optimizer="adagrad", distribution="a2a",
                      kernel_impl=kernel_impl)


def make_source(cfg, seed: int, batch: int = BATCH, **kw):
    from repro.data import get_source

    return get_source("zipf_sparse", batch_size=batch,
                      num_features=cfg.num_features, features_per_sample=K,
                      signal_features=4096, seed=seed, **kw)


def peak_hbm(label: str) -> None:
    import jax

    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        print(f"[{label}] {d} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')}",
              flush=True)


def train(label: str, cfg, mesh, hot, seed: int):
    """STEPS fit_sgd steps from zeros; returns (engine, losses)."""
    from repro.api import DPMREngine, ShardedLoader
    from repro.runtime import spans

    loader = ShardedLoader(make_source(cfg, seed), mesh, prefetch=2)
    engine = DPMREngine(cfg, mesh, hot_ids=hot)
    t0 = time.perf_counter()
    hist = engine.fit_sgd(loader, steps=1)
    first = time.perf_counter() - t0
    spans.reset()
    hist += engine.fit_sgd(loader, steps=STEPS - 1)
    step = spans.totals()["spans"]
    steady = sum(step[k]["s"] for k in ("dpmr.dispatch", "dpmr.metrics_sync")
                 ) / (STEPS - 1)
    for h in hist:
        print(f"[{label}] step {h['step']} loss {h['loss']!r} "
              f"overflow {h['overflow']}", flush=True)
    print(f"[{label}] P={engine.fns.num_shards} capacity="
          f"{engine.fns.capacity} first step (compile + run) {first:.3f} s, "
          f"then {steady * 1e3:.3f} ms/step in dpmr.dispatch + "
          "dpmr.metrics_sync (host clock, informational)", flush=True)
    overflow = sum(h["overflow"] for h in hist)
    if overflow:
        raise SmokeFailure(f"[{label}] {overflow} features overflowed the "
                           "exchange; the comparison needs 0")
    return engine, [h["loss"] for h in hist]


def compare(label: str, losses, want_losses, table, want_table) -> None:
    """Losses within RTOL of each other, parameters within RTOL in relative
    L2 norm and ATOL in max abs error; raises on a mismatch."""
    import numpy as np

    losses, want_losses = np.asarray(losses), np.asarray(want_losses)
    table, want_table = np.asarray(table), np.asarray(want_table)
    loss_rel = float(np.max(np.abs(losses - want_losses)
                            / np.abs(want_losses)))
    err = np.abs(table - want_table)
    worst = int(np.argmax(err))
    l2_rel = float(np.linalg.norm(err) / max(np.linalg.norm(want_table),
                                             1e-30))
    ok = (loss_rel <= RTOL and l2_rel <= RTOL and float(err[worst]) <= ATOL
          and bool(np.all(np.isfinite(table))))
    print(f"[{label}] losses max rel err {loss_rel!r}; params rel L2 err "
          f"{l2_rel!r}, max abs err {float(err[worst])!r} at id {worst} "
          f"({float(table[worst])!r} vs {float(want_table[worst])!r}), "
          f"nonzero {int(np.count_nonzero(table))}; limits rel {RTOL} "
          f"abs {ATOL}: {'PASS' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SmokeFailure(f"[{label}] mismatch")


def run_reference(cfg, seed: int, f: int):
    from repro.core import reference

    theta, _, losses = reference.sgd_steps(
        cfg, make_source(cfg, seed).iter_batches(limit=STEPS), f)
    return losses, theta


def serve(cfg, mesh, engine, seed: int) -> None:
    """Async save -> restore into a server -> two client threads; every
    answer bit-identical to the trained engine's `predict`."""
    import numpy as np

    from repro.core import hot_sharding
    from repro.serve import BatchingConfig, DPMRServeEngine, HotCacheConfig

    sizes = [REQUEST_ROWS[i % len(REQUEST_ROWS)] for i in range(REQUESTS)]
    # held-out rows (the corpus past the training batches)
    test = make_source(cfg, seed, sum(sizes) + 2 * HOT_REQUESTS,
                       start=1000).batch(0)
    ids, vals = test["ids"], test["vals"]
    reqs, at = [], 0
    for r in sizes:
        reqs.append({"ids": ids[at:at + r], "vals": vals[at:at + r]})
        at += r
    # one-row requests cut to the held-out head: the hot cache answers
    # these without the exchange, through its own copy of the predict head
    head = hot_sharding.select_hot(ids, 0.0, HEAD)
    keep = np.isin(ids[at:], head)
    rows = np.flatnonzero(keep.any(axis=1))[:HOT_REQUESTS] + at
    for i in rows:
        k = np.isin(ids[i:i + 1], head)
        reqs.append({"ids": np.where(k, ids[i:i + 1], -1),
                     "vals": np.where(k, vals[i:i + 1], 0.0)})
    order = np.random.default_rng(seed).permutation(len(reqs))
    reqs = [reqs[i] for i in order]
    with tempfile.TemporaryDirectory(prefix="dpmr_smoke_ckpt_") as ckdir:
        t0 = time.perf_counter()
        step = engine.save(ckdir, block=False)
        stall = time.perf_counter() - t0
        engine.wait_saves()
        print(f"[serve] saved step {step} (save call {stall:.3f} s, "
              f"written {time.perf_counter() - t0:.3f} s)", flush=True)
        srv = DPMRServeEngine.from_checkpoint(
            cfg, mesh, ckdir,
            batching=BatchingConfig(max_batch=64, max_wait_ms=2.0),
            hot_cache=HotCacheConfig(refresh_every=8))
    answers: list = [None] * len(reqs)

    def client(part: int) -> None:
        futs = [(i, srv.submit(reqs[i]["ids"], reqs[i]["vals"]))
                for i in range(part, len(reqs), 2)]
        for i, fut in futs:
            answers[i] = np.asarray(fut.result())

    try:
        threads = [threading.Thread(target=client, args=(p,))
                   for p in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        srv.stop()
    bad = {}
    for i, req in enumerate(reqs):
        want = engine.predict(req)
        if answers[i] is None or not np.array_equal(answers[i], want):
            bad[i] = None if answers[i] is None else int(np.max(np.abs(
                answers[i].view(np.int32) - want.view(np.int32))))
    snap = srv.metrics_snapshot()
    print(f"[serve] {len(reqs)} requests ({sum(len(r['ids']) for r in reqs)}"
          f" rows, {len(rows)} cut to the {HEAD} head features) from 2 "
          f"threads: {len(reqs) - len(bad)} bit-identical to "
          f"engine.predict; cache hits {snap.get('cache_hits', 0)}, "
          f"flushes {snap.get('flushes', 'n/a')}: "
          f"{'PASS' if not bad else 'FAIL'}", flush=True)
    if bad:
        raise SmokeFailure(f"[serve] requests differ from predict "
                           f"(request: max ulps) {bad}")


def one_chip(seed: int) -> None:
    from repro.api import hot_ids_from_corpus
    from repro.core import dpmr, reference
    from repro.launch.mesh import make_host_mesh

    cfg = make_cfg()
    mesh = make_host_mesh(1, 1)
    hot = hot_ids_from_corpus(
        cfg, make_source(cfg, seed).iter_batches(limit=4), mesh)
    engine, losses = train("train xla", cfg, mesh, hot, seed)
    table = reference.engine_table(engine.state)
    peak_hbm("train xla")

    ref_losses, theta = run_reference(cfg, seed,
                                      dpmr.padded_features(cfg, mesh))
    compare("reference", losses, ref_losses, table, theta)
    del theta

    pengine, plosses = train("train pallas", make_cfg("pallas"), mesh, hot,
                             seed)
    compare("pallas vs xla", plosses, losses,
            reference.engine_table(pengine.state), table)
    del pengine, table

    serve(cfg, mesh, engine, seed)
    peak_hbm("all phases")


def four_chips(seed: int) -> None:
    import jax

    from repro.api import hot_ids_from_corpus
    from repro.core import dpmr, reference
    from repro.launch.mesh import make_host_mesh

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    cfg = make_cfg()
    mesh4 = make_host_mesh(1, 4)
    mesh1 = make_host_mesh(1, 1)           # jax.devices()[:1]
    hot = hot_ids_from_corpus(
        cfg, make_source(cfg, seed).iter_batches(limit=4), mesh4)
    engine4, losses4 = train("train 4 chips", cfg, mesh4, hot, seed)
    table4 = reference.engine_table(engine4.state)
    del engine4
    peak_hbm("train 4 chips")
    engine1, losses1 = train("train 1 chip", cfg, mesh1, hot, seed)
    table1 = reference.engine_table(engine1.state)
    del engine1
    ref_losses, theta = run_reference(cfg, seed,
                                      dpmr.padded_features(cfg, mesh4))
    compare("4 chips vs reference", losses4, ref_losses, table4, theta)
    compare("1 chip vs reference", losses1, ref_losses, table1, theta)
    compare("4 chips vs 1 chip", losses4, losses1, table4, table1)
    peak_hbm("all phases")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = device_line()
        from repro.runtime import compile_cache

        print(f"compile cache: {compile_cache.enable()}", flush=True)
        print(f"model: 2^{LOG2_FEATURES} features, K={K}, batch {BATCH}, "
              f"{STEPS} steps, adagrad, a2a, seed {args.seed}", flush=True)
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
