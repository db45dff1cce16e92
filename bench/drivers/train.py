"""Training driver: the body of `DPMREngine.fit_sgd` on a seeded corpus.

Set-up builds one engine and one `ShardedLoader` over a pool of batches
made from the seed, and drives the engine's own `train_step` through its
first three steps on the pool's first three batches; the first compiles.
The same engine and loader then run the window: `next()` on the loader
and `engine.train_step`, as `fit_sgd` does, for `--seconds`, ending in
`block_until_ready` on the state. The pool is cycled in epochs, as the
paper's trainer loops over its corpus.

What is compared, once the window has closed and the engine is freed,
with the plain float64 reference over the same three batches:
  loss_gap    the largest relative gap of the three steps' losses
  grad_gap    the first step's gradient, as adagrad holds it (its
              accumulator after one step is g^2): the gap between the
              program's and the reference's norm, by leaf (cold table,
              hot table), over the larger of the leaf's and the median
              leaf's reference norm; the worst leaf
  change_gap  the same for the parameters' change after three steps
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from bench import common, reference

STEPS_COMPARED = 3


def make_pool(config: dict, traffic: dict, seed: int) -> list[dict]:
    corpus = config["corpus"]
    gen = common.load_module("traffic", corpus["generator"])
    return [gen.make_batch(corpus, int(traffic["global_batch"]),
                           common.sub_seed(seed, 1, i))
            for i in range(int(traffic["pool_batches"]))]


def _pool_source(pool: list[dict]):
    from repro.data import DataSource

    class PoolSource(DataSource):
        """The benchmark's corpus: a fixed pool of host batches."""

        name = "bench_pool"

        def __init__(self):
            self.batch_size = len(pool[0]["labels"])
            self.num_batches = len(pool)

        def batch(self, index: int) -> dict:
            self._check_index(index)
            return pool[index]

    return PoolSource()


def _norms():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(state):
        def n(x):
            return jnp.sqrt(jnp.sum(jnp.square(x)))

        return {"grad": {"cold": jnp.sqrt(jnp.sum(state.cold_acc)),
                         "hot": jnp.sqrt(jnp.sum(state.hot_acc))},
                "change": {"cold": n(state.cold), "hot": n(state.hot)}}

    return norms


def first_steps(engine, batches, n: int = STEPS_COMPARED) -> dict:
    """The engine's first `n` steps: losses, the gradient's norm per leaf
    after step 1 and the parameters' change per leaf after step n (the
    state starts at zero, so the change is the state itself)."""
    import jax

    norms = _norms()
    losses, grad = [], None
    for i in range(n):
        losses.append(engine.train_step(next(batches))["loss"])
        if i == 0:
            grad = jax.device_get(norms(engine.state)["grad"])
    change = jax.device_get(norms(engine.state)["change"])
    return {"losses": losses,
            "grad": {k: float(v) for k, v in grad.items()},
            "change": {k: float(v) for k, v in change.items()}}


def reference_readings(cfg_model: dict, batches: list[dict],
                       hot_sample: list[dict],
                       precision: str = "float64") -> dict:
    """The same readings from the plain reference, split into leaves by
    the reference's own choice of hot ids."""
    ref = reference.train_steps(batches, cfg_model["learning_rate"],
                                cfg_model["adagrad_eps"], precision)
    hot = reference.hot_set(
        np.concatenate([b["ids"].reshape(-1) for b in hot_sample]),
        cfg_model["hot_threshold"], cfg_model["max_hot"])
    is_hot = np.isin(ref["ids"], hot)

    def by_leaf(x):
        return {"cold": float(np.linalg.norm(x[~is_hot])),
                "hot": float(np.linalg.norm(x[is_hot]))}

    return {"losses": ref["losses"], "grad": by_leaf(ref["grad1"]),
            "change": by_leaf(ref["theta"])}


def gaps(prog: dict, ref: dict) -> dict:
    """loss_gap, grad_gap and change_gap of the program's readings against
    the reference's. Leaves whose reference gradient is under a thousandth
    of the median leaf's move by round-off alone and are left out of
    change_gap."""
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out = {"loss_gap": float(np.max(np.abs(lp - lr_) / np.abs(lr_)))}
    grad_med = statistics.median(ref["grad"].values())
    moving = [k for k, v in ref["grad"].items() if v >= 1e-3 * grad_med]
    for what, leaves in (("grad", list(ref["grad"])), ("change", moving)):
        med = statistics.median(ref[what][k] for k in leaves)
        out[f"{what}_gap"] = max(
            abs(prog[what][k] - ref[what][k]) / max(ref[what][k], med)
            for k in leaves)
    return out


def run(ctx: dict) -> dict:
    phase = common.Phases(ctx.setdefault("setup_phases", {}))
    import jax

    from repro.api import DPMREngine, ShardedLoader, hot_ids_from_corpus
    from repro.launch.mesh import make_host_mesh

    phase("imports")
    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    cfg = common.model_config(config)
    mesh = make_host_mesh(1, ctx["chips"])
    pool = make_pool(config, traffic, seed)
    phase("pool")
    hot_sample = pool[:int(traffic["hot_sample_batches"])]
    hot = hot_ids_from_corpus(cfg, hot_sample, mesh)
    phase("hot_ids")
    engine = DPMREngine(cfg, mesh, hot_ids=hot)
    jax.block_until_ready(engine.state)
    phase("state")
    loader = ShardedLoader(_pool_source(pool), mesh, prefetch=2,
                           host_index=0, num_hosts=1)
    batches = loader.batches()
    prog = first_steps(engine, batches)
    jax.block_until_ready(engine.state)
    phase("first_steps")

    spans = common.Spans(annotate=ctx["trace"])
    steps = failed = 0
    ctx["window_open"]()
    t0 = time.perf_counter()
    with spans("bench.window"):
        while True:
            with spans("bench.loader_next"):
                batch = next(batches)
            with spans("bench.train_step"):
                m = engine.train_step(batch)
            steps += 1
            failed += not np.isfinite(m["loss"])
            if time.perf_counter() - t0 >= ctx["seconds"]:
                break
        jax.block_until_ready(engine.state)
    window_s = time.perf_counter() - t0
    ctx["window_closed"]()
    hlo = []
    if ctx["trace"]:
        fns = engine.step_fns(int(traffic["global_batch"]))
        hlo.append(fns.train_step.lower(engine.state, batch).compile()
                   .as_text())
    batches.close()
    peak = common.memory_peak_bytes()
    del engine, loader, batches
    gc.collect()

    ref = reference_readings(config["model"], pool[:STEPS_COMPARED],
                             hot_sample)
    found = gaps(prog, ref)
    limits = ctx["limits"]
    checks = {k: (found[k], float(limits[k])) for k in found}
    control = None
    if ctx.get("control"):
        control = gaps(reference_readings(
            config["model"], pool[:STEPS_COMPARED], hot_sample,
            "bfloat16"), ref)
    b = int(traffic["global_batch"])
    record = {"steps": steps, "window_s": window_s, "global_batch": b,
              "spans": spans.as_dict(), "hlo": hlo}
    if ctx["trace"]:
        from bench import work

        per_batch = [work.step_bytes(p["ids"]) for p in pool]
        record["work_bytes"] = sum(
            per_batch[(STEPS_COMPARED + i) % len(pool)]
            for i in range(steps))
    return {"end_to_end": {"train_samples_per_s": steps * b / window_s},
            "attempted": steps, "failed": failed, "memory_peak_bytes": peak,
            "record": record, "checks": checks, "control": control}
