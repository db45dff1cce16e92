"""End-to-end training driver (host-scale; full configs go through dryrun).

Wires together: model zoo, DPMR-dense sharded trainer, the `repro.data`
plane (lm_markov source + prefetching ShardedLoader with a resumable
cursor), checkpoint manager (atomic/keep-N/async), preemption guard,
straggler watchdog, and resume (model + optimizer + exact data position).

`--sparse` drives the paper's sparse face instead (DPMREngine over a
zipf_sparse loader); `--strategy` selects any registered distribution
strategy (a2a | allgather | psum_scatter | hier_a2a | compressed_reduce |
topk_reduce | overlap_a2a | user-registered) and engine save()/restore()
carries the model, the strategy carry (compression error feedback /
top-k sparsification residual), and the data cursor.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --sparse \
      --strategy compressed_reduce --steps 40 --batch 512 --ckpt /tmp/ck
  # kill either mid-run; rerun the same command: it resumes from the ckpt
"""
from __future__ import annotations

import argparse
import collections
import hashlib
import json
import logging

import jax
import numpy as np

from repro.ckpt.checkpointer import Checkpointer
from repro.configs.base import ParallelConfig, TrainConfig
from repro.data import Cursor, ShardedLoader, get_source
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.runtime import compile_cache, spans
from repro.runtime.fault_tolerance import PreemptionGuard, StragglerWatchdog
from repro.train import trainer

log = logging.getLogger("repro.train")


def make_loader(args, cfg, mesh=None) -> ShardedLoader:
    """The driver's data plane: lm_markov source (with encoder frames for
    encdec families) behind a prefetching loader. Batches stay host-shaped
    ("device" placement) — the jitted trainer step owns distribution.
    Pinned to a single stream (host 0 of 1): every process must feed the
    jitted step identical global batches, exactly as the pre-loader driver
    did; per-host disjoint shards need global-array placement first."""
    source = get_source(
        "lm_markov", vocab_size=cfg.vocab_size, seq_len=args.seq,
        batch_size=args.batch, seed=args.data_seed,
        encdec_d_model=cfg.d_model if cfg.family == "encdec" else 0)
    return ShardedLoader(source, mesh, placement="device",
                         host_index=0, num_hosts=1,
                         prefetch=args.prefetch)


def log_step_spans() -> None:
    """Host time per step since the last `spans.reset()`, by the
    program's spans, and its counters."""
    t = spans.totals()
    c = collections.Counter(t["counts"])
    ms = {k: t["spans"].get(k, {"s": 0.0})["s"] / max(c["dpmr.steps"], 1)
          * 1e3 for k in ("dpmr.dispatch", "dpmr.metrics_sync",
                          "loader.wait")}
    log.info("%d steps (%d step fns built, %d batches loaded), per step: "
             "dispatch %.3f ms, metrics_sync %.3f ms, loader.wait %.3f ms; "
             "dpmr.overflow %d, loader.starved %d", c["dpmr.steps"],
             c["dpmr.step_fns_built"], c["loader.batches"],
             ms["dpmr.dispatch"], ms["dpmr.metrics_sync"],
             ms["loader.wait"], c["dpmr.overflow"], c["loader.starved"])


def sparse_loop(args) -> dict:
    """Sparse-face driver: DPMREngine + zipf_sparse loader (or, with
    --data-dir, a file_sparse corpus under chunk-aligned shard ownership),
    strategy by name (--strategy), resumable via engine save()/restore()
    (state incl. the strategy carry + the loader cursor).

    Three execution modes over one loop (docs/DISTRIBUTED.md):
      * --hosts H --host-id h: single-process EMULATION of host h — the
        loader serves only that host's shard (its owned chunk range for
        file corpora, its batch stride otherwise);
      * --hosts H --host-id -1: all-hosts emulation — one process serves
        the concatenated H*B-row global batch every step, the parity
        baseline a real H-process run must bit-match;
      * --coordinator/--num-processes/--process-id (one invocation per
        process): REAL `jax.distributed` execution — process h is host h,
        its loader materializes only host h's batches, and the placement
        seam assembles them into global arrays
        (`runtime/multiprocess.global_batch_placement`)."""
    from repro.api import DPMREngine, ShardedLoader, get_source, get_strategy
    from repro.ckpt.checkpointer import Checkpointer as Ck
    from repro.configs.base import DPMRConfig
    from repro.runtime import multiprocess as mp

    ctx = mp.context()
    hosts, host_id = args.hosts, args.host_id
    if ctx.is_distributed:
        if hosts not in (1, ctx.num_processes) or host_id == -1:
            raise SystemExit(
                "real multi-process runs derive the data plane from the "
                "process topology: drop --hosts/--host-id (process h IS "
                "host h of --num-processes)")
        hosts, host_id = ctx.num_processes, ctx.process_id
    get_strategy(args.strategy)          # fail fast on unknown names
    mesh = make_host_mesh(args.mesh_data, args.mesh_model)
    cfg = DPMRConfig(num_features=args.features,
                     max_features_per_sample=32,
                     distribution=args.strategy, optimizer="adagrad",
                     learning_rate=args.lr)
    if args.data_dir:
        source = get_source("file_sparse", directory=args.data_dir)
    else:
        source = get_source("zipf_sparse", batch_size=args.batch,
                            num_batches=args.sparse_batches,
                            num_features=args.features,
                            features_per_sample=32, seed=args.data_seed)
    eval_source = source         # deterministic final eval reads raw batches
    if host_id == -1:
        # parity baseline: one process, every host's stream, concatenated
        source = mp.emulate_all_hosts(source, hosts)
        hosts, host_id = 1, 0
    loader = ShardedLoader(
        source, mesh, host_index=host_id, num_hosts=hosts,
        prefetch=args.prefetch, shuffle=args.shuffle,
        placement=mp.global_batch_placement(mesh) if ctx.is_distributed
        else "sharded")
    if loader.assignment is not None and loader.assignment.kind == "chunk":
        log.info("chunk ownership: host %d/%d owns chunks [%d, %d) of %d",
                 host_id, hosts,
                 loader.assignment.owned_chunks(host_id).start,
                 loader.assignment.owned_chunks(host_id).stop,
                 loader.assignment.num_chunks)
    engine = DPMREngine(cfg, mesh)
    if args.ckpt and Ck(args.ckpt).latest_step() is not None:
        # reassign rather than refuse when --hosts changed between runs:
        # the loop resumes at the epoch boundary under the new ownership
        engine.restore(args.ckpt, loader=loader, on_host_change="reassign")
        log.info("resumed sparse run at step %d (strategy %s)",
                 int(engine.state.step), args.strategy)
    # checkpoint every --save-every steps (like the dense loop), so a
    # killed run resumes mid-stream instead of restarting from step 0.
    # --async-ckpt keeps only the device->host snapshot on the step path;
    # the final save is always blocking (flushes any in-flight write)
    history = []
    while int(engine.state.step) < args.steps:
        chunk = min(args.save_every, args.steps - int(engine.state.step))
        spans.reset()
        history += engine.fit_sgd(loader, steps=chunk)
        log_step_spans()
        if args.ckpt:
            engine.save(args.ckpt, keep=args.keep,
                        block=not args.async_ckpt)
    if args.ckpt and args.async_ckpt:
        engine.save(args.ckpt, keep=args.keep)      # blocking flush
    try:
        # the most recently used compilation — the CONFORMED global batch
        # size fit_sgd actually trained on (the raw source batch size may
        # not divide the mesh and would fail make_step_fns' divisibility
        # assert)
        fns = engine.fns
    except RuntimeError:
        # nothing trained this run (restored at/after --steps): compile at
        # the size the loader would serve
        bs = int(getattr(loader.source, "batch_size", 0)) or args.batch
        fns = engine.step_fns(bs - bs % loader.batch_divisor or bs)
    wire = get_strategy(args.strategy).bytes_per_device(fns.ctx)
    # deterministic parity probe: the pmean loss METRIC can wobble ~1 ulp
    # across process boundaries (reduction order), so cross-mode parity is
    # asserted on the final parameters (digest) and on a loss recomputed
    # host-side in float64 over a fixed raw batch — bit-identical exactly
    # when the parameters are (scripts/check_multiprocess.py)
    eval_batch = eval_source.batch(0)
    probs = np.asarray(engine.predict({"ids": eval_batch["ids"],
                                       "vals": eval_batch["vals"]}),
                       np.float64)
    y = np.asarray(eval_batch["labels"], np.float64)
    eps = 1e-9
    final_eval = float(-np.mean(y * np.log(probs + eps)
                                + (1 - y) * np.log(1 - probs + eps)))
    digest = hashlib.md5(
        mp.host_value(engine.state.cold).tobytes()).hexdigest()
    return {"history": history, "last_step": int(engine.state.step),
            "strategy": args.strategy,
            "wire_bytes": {"inner": wire.inner, "outer": wire.outer},
            "losses": [h["loss"] for h in history],
            "final_eval_loss": final_eval, "cold_md5": digest,
            "num_processes": ctx.num_processes,
            "process_id": ctx.process_id, "hosts": hosts}


def train_loop(args, fail_injector=None) -> dict:
    mesh = make_host_mesh(args.mesh_data, args.mesh_model)
    cfg = registry.smoke_config(args.arch) if args.smoke else \
        registry.get_spec(args.arch).cfg
    spec = registry.get_spec(args.arch)
    tc = TrainConfig(learning_rate=args.lr, warmup_steps=args.warmup,
                     total_steps=args.steps, optimizer=args.optimizer)
    pc = ParallelConfig(microbatches=args.microbatches)
    loader = make_loader(args, cfg, mesh)
    ck = Checkpointer(args.ckpt, keep=args.keep) if args.ckpt else None
    guard = PreemptionGuard() if args.preemption_guard else None
    watchdog = StragglerWatchdog()

    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, tc, pc,
                                   jax.random.PRNGKey(tc.seed))
        start_step = 0
        if ck is not None and ck.latest_step() is not None:
            state, manifest = ck.restore(state)
            extra = manifest["extra"]
            if "data" in extra:                      # cursor-carrying ckpt
                loader.load_state_dict(extra["data"])
                start_step = loader.cursor.step
            else:                                    # pre-data-plane ckpt
                start_step = extra["data_step"]
                loader.seek(Cursor(0, start_step))
            log.info("resumed from step %d", start_step)
        step_fn = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))

        def save(step, block):
            ck.save(step, state,
                    extra={"data_step": step, "data": loader.state_dict()},
                    block=block)

        losses = []
        i = start_step
        for batch in loader.batches(args.steps - start_step):
            watchdog.step_start()
            if fail_injector is not None:
                fail_injector.maybe_fail(i)
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            watchdog.step_end(i)
            i += 1
            if args.log_every and i % args.log_every == 0:
                log.info("step %d loss %.4f lr %.2e", i, loss,
                         float(metrics["lr"]))
            if ck is not None and (i % args.save_every == 0
                                   or i == args.steps):
                save(i, block=not args.async_ckpt)
            if guard is not None and guard.preempted():
                if ck is not None:
                    save(i, block=True)
                log.warning("preempted; saved at step %d", i)
                break
        if ck is not None:
            ck.wait()
    return {"state": state, "losses": losses, "last_step": i,
            "straggler_events": watchdog.events}


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="model zoo id (dense face; required "
                                   "unless --sparse)")
    ap.add_argument("--sparse", action="store_true",
                    help="train the DPMR sparse face (DPMREngine over a "
                         "zipf_sparse loader) instead of a zoo model")
    ap.add_argument("--strategy", default="a2a",
                    help="sparse-face distribution strategy (any name in "
                         "repro.api.list_strategies())")
    ap.add_argument("--features", type=int, default=1 << 14,
                    help="sparse-face hashed feature-space size")
    ap.add_argument("--sparse-batches", type=int, default=64,
                    help="sparse-face corpus size in batches (one epoch)")
    ap.add_argument("--data-dir", default="",
                    help="sparse face: read a file_sparse corpus (written "
                         "by write_file_corpus) from this directory under "
                         "chunk-aligned shard ownership instead of the "
                         "synthetic zipf_sparse stream")
    ap.add_argument("--hosts", type=int, default=1,
                    help="simulate a data plane divided over this many "
                         "hosts (this process serves one of them)")
    ap.add_argument("--host-id", type=int, default=0,
                    help="which host of --hosts this process simulates; "
                         "-1 emulates ALL hosts in one process (the "
                         "concatenated global batch — the parity baseline "
                         "for a real --num-processes run)")
    ap.add_argument("--coordinator", default="",
                    help="jax.distributed coordinator address host:port "
                         "(process 0 serves it); required with "
                         "--num-processes > 1")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="total processes in a REAL multi-process run "
                         "(one launch/train.py invocation per process; "
                         "sparse face only — see docs/DISTRIBUTED.md)")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this process's rank in [0, --num-processes)")
    ap.add_argument("--local-devices", type=int, default=0,
                    help="force this process's emulated CPU device count "
                         "(XLA_FLAGS host-platform trick; 0 = leave the "
                         "environment alone). Global mesh devices = "
                         "--local-devices x --num-processes")
    ap.add_argument("--json", action="store_true",
                    help="print the run summary as one JSON line (losses, "
                         "final_eval_loss, cold_md5) — what the parity "
                         "checkers consume")
    ap.add_argument("--shuffle", action="store_true",
                    help="per-epoch loader shuffling (seeded, resume-exact)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--data-seed", type=int, default=0)
    ap.add_argument("--prefetch", type=int, default=2,
                    help="loader prefetch depth (0 = synchronous input)")
    ap.add_argument("--log-every", type=int, default=10)
    # BooleanOptionalAction so --no-preemption-guard is expressible
    # (store_true with default=True could never be disabled)
    ap.add_argument("--preemption-guard",
                    action=argparse.BooleanOptionalAction, default=True)
    return ap


def main():
    logging.basicConfig(level=logging.INFO)
    args = build_parser().parse_args()
    compile_cache.enable()
    if args.num_processes > 1 or args.local_devices:
        # must run before the first jax computation (backend init reads
        # XLA_FLAGS once; jax.distributed must precede any collective)
        from repro.runtime import multiprocess

        multiprocess.initialize(
            coordinator=args.coordinator,
            num_processes=args.num_processes, process_id=args.process_id,
            local_device_count=args.local_devices or None)
    if args.sparse:
        out = sparse_loop(args)
        wb = out["wire_bytes"]
        print(f"[{out['strategy']}] final loss "
              f"{out['losses'][-1] if out['losses'] else float('nan'):.4f} "
              f"after {out['last_step']} steps; wire bytes/device/step "
              f"inner={wb['inner']} outer={wb['outer']}")
        if args.json:
            out.pop("history", None)
            print(json.dumps(out))
        return
    if args.num_processes > 1:
        raise SystemExit("--num-processes applies to the sparse face "
                         "(--sparse); the dense driver is single-process")
    if not args.arch:
        raise SystemExit("--arch is required (or pass --sparse)")
    out = train_loop(args)
    print(f"final loss {out['losses'][-1]:.4f} after {out['last_step']} steps")


if __name__ == "__main__":
    main()
