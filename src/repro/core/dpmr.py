"""Distributed Parameter Map-Reduce — the paper's engine on TPU collectives.

Algorithm 1/8 of the paper as a shard_map program over ALL mesh axes (every
device is a DPMR node holding both a sample shard and a parameter shard,
exactly the paper's HDFS co-location):

  stage                 paper          here (per train step)
  -----                 -----          ----
  initParameters        Algorithm 2    init_state (zeros; hot stats external)
  invertDocuments       Algorithm 3    sparse.route_build (sort-by-feature)
  distributeParameters  Algorithm 4    all_to_all(requests) + owner lookup
                                       + all_to_all(responses)
  restoreDocuments      Algorithm 5    sparse.route_return (unsort)
  computeGradients      Algorithm 6    kernels.ops.sigmoid_grad (map body)
                                       + sparse.combine_grads (combiner)
  (reduce shuffle)                     all_to_all(grad sums) + owner
                                       scatter-add
  updateParameters      Algorithm 7    sharded SGD on the owner shard
  hot sharding          §4             hot set replicated, grads psum'd
                                       (see core.hot_sharding)

The distributeParameters / gradient-reduce collectives are pluggable
`DistributionStrategy` objects looked up by name from `repro.api.strategies`
(cfg.distribution: "a2a" | "allgather" | "psum_scatter" | "hier_a2a" |
"compressed_reduce" | "topk_reduce" | "overlap_a2a" | registered
compositions like "hier_a2a+topk" | anything third parties register |
"auto", which asks `repro.api.autotune` for the cheapest strategy under
the analytic per-tier wire-cost model — see `resolve_distribution`).
Strategies see the
mesh's wire tiers — `launch.mesh.tier_axes` factors the axes into the
DCN-crossing outer tier (`pod`) and the ICI inner tier, carried on the
`StrategyContext` — and may keep persistent per-device state (`init_carry`,
e.g. compression error feedback) which lives in `DPMRState.strat`, is
updated by `train_step`, and is checkpointed with the rest of the state.
The optimizer applied in updateParameters and the learning-rate schedule
come from the shared `repro.optim` registries, so the sparse face selects
them exactly like the dense trainer does.
"""
from __future__ import annotations

from collections.abc import Callable
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import DPMRConfig
from repro.core import hot_sharding
from repro.kernels import ops
from repro.optim import optimizers, schedules


class DPMRState(NamedTuple):
    cold: jax.Array       # (F,) f32, sharded over all mesh axes
    hot: jax.Array        # (max_hot,) f32, replicated (Zipf head)
    hot_ids: jax.Array    # (max_hot,) int32 sorted, INT_MAX padded, replicated
    cold_acc: jax.Array   # (F,) adagrad accumulator, sharded like cold
    hot_acc: jax.Array    # (max_hot,) adagrad accumulator, replicated
    step: jax.Array       # () int32
    strat: jax.Array      # (P*L,) f32 per-device strategy carry (L from
    #                       strategy.init_carry; (P,) zeros when stateless),
    #                       sharded over all mesh axes like cold


def _axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def num_shards(mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        n *= int(mesh.shape[a])
    return n


def padded_features(cfg: DPMRConfig, mesh) -> int:
    p = num_shards(mesh)
    return -(-cfg.num_features // p) * p


def capacity_for_shards(cfg: DPMRConfig, batch_local: int, p: int,
                        factor: float = 4.0) -> int:
    """`capacity` for an analytic shard count (no mesh required)."""
    n = batch_local * cfg.max_features_per_sample
    mean = max(1, n // p)
    return int(min(n, max(16, -(-int(factor * mean) // 8) * 8)))


def capacity(cfg: DPMRConfig, batch_local: int, mesh,
             factor: float = 4.0) -> int:
    """Per-(src,dst) a2a slots for cold features: factor x the uniform mean."""
    return capacity_for_shards(cfg, batch_local, num_shards(mesh), factor)


def make_strategy_context(cfg: DPMRConfig, mesh, cap: int = 0,
                          kernel_impl: str | None = None):
    """The `StrategyContext` for this (cfg, mesh) geometry: all mesh axes,
    factored into the (outer=DCN, inner=ICI) wire tiers by
    `launch.mesh.tier_axes`. `cap` is the per-(src,dst) a2a capacity
    (batch-size dependent; 0 where only the static geometry matters).
    `kernel_impl` overrides `cfg.kernel_impl` (None = use the config)."""
    # late import: repro.api.strategies imports from repro.core
    from repro.api.strategies import StrategyContext
    from repro.kernels import ops
    from repro.launch.mesh import tier_axes, tier_shards

    outer, inner = tier_axes(mesh)
    po, _ = tier_shards(mesh)
    p = num_shards(mesh)
    impl = ops.normalize_impl(
        cfg.kernel_impl if kernel_impl is None else kernel_impl)
    return StrategyContext(axes=_axes(mesh), num_shards=p,
                           block_size=padded_features(cfg, mesh) // p,
                           capacity=cap, inner_axes=inner, outer_axes=outer,
                           outer_shards=po, topk_frac=cfg.topk_frac,
                           kernel_impl=impl)


_AUTOTUNE_BATCH_LOCAL = 128
#   nominal per-device batch behind cfg.distribution == "auto": the
#   autotuner prices capacity at this fixed size so one (cfg, mesh) pair
#   resolves to ONE strategy — a batch-size-dependent choice could flip
#   between StepFns compilations and invalidate the persistent carry shape


def resolve_distribution(cfg: DPMRConfig, mesh) -> str:
    """The concrete strategy name for this (cfg, mesh): cfg.distribution
    itself, or — when it is the sentinel `"auto"` — the cheapest
    registered strategy under the analytic per-tier wire-cost model
    (`repro.api.autotune.choose_strategy`) on this mesh's geometry."""
    if cfg.distribution != "auto":
        return cfg.distribution
    # late import: repro.api imports this module
    from repro.api import autotune

    ctx = make_strategy_context(
        cfg, mesh, cap=capacity(cfg, _AUTOTUNE_BATCH_LOCAL, mesh))
    return autotune.choose_strategy(ctx)


def strategy_carry_len(cfg: DPMRConfig, mesh) -> int:
    """Per-device length L of the resolved strategy's persistent carry (1
    when the strategy is stateless; the placeholder keeps the state pytree
    shape-stable across strategies at negligible cost)."""
    from repro.api.strategies import get_strategy

    # shape only: a stateful carry is (F,)-sized, never build it here
    strategy = get_strategy(resolve_distribution(cfg, mesh))
    ctx = make_strategy_context(cfg, mesh)
    carry = jax.eval_shape(lambda: strategy.init_carry(ctx))
    return 1 if carry is None else int(carry.shape[0])


def _zeros(n: int, sharding) -> jax.Array:
    """(n,) f32 zeros created in place under `sharding`: each device
    writes only its own shard, nothing is built whole on one device."""
    return jax.jit(functools.partial(jnp.zeros, (n,), jnp.float32),
                   out_shardings=sharding)()


def init_state(cfg: DPMRConfig, mesh, hot_ids=None) -> DPMRState:
    f = padded_features(cfg, mesh)
    axes = _axes(mesh)
    shard = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    if hot_ids is None:
        hot_ids = jnp.full((cfg.max_hot,), hot_sharding.INT_MAX, jnp.int32)
    hot_ids = jax.device_put(jnp.asarray(hot_ids).astype(jnp.int32), rep)
    strat_len = num_shards(mesh) * strategy_carry_len(cfg, mesh)
    return DPMRState(cold=_zeros(f, shard), hot=_zeros(cfg.max_hot, rep),
                     hot_ids=hot_ids, cold_acc=_zeros(f, shard),
                     hot_acc=_zeros(cfg.max_hot, rep),
                     step=jnp.zeros((), jnp.int32),
                     strat=_zeros(strat_len, shard))


def optimize(cfg: DPMRConfig, theta, acc, grad, lr):
    """Algorithm 7 step 12: newPara = optimize(para, grad).

    Delegates to the shared sparse-optimizer registry (optim/optimizers.py),
    so the sparse face selects optimizers by name like the dense trainer.
    """
    return optimizers.get_sparse_optimizer(cfg.optimizer).update(
        theta, acc, grad, lr, cfg)


def predict_probs(vals, theta):
    """Algorithm 9's head: sigmoid(sum_k vals * theta) per row.

    The serving hot cache answers with this same function on mirrored
    parameters, and its answers must equal the device predict bit for bit
    on every backend. XLA picks a reduction order from the layout and may
    contract a multiply into an add, both differently in different
    programs; so the products are materialized behind a barrier and summed
    in a fixed pairwise order of explicit adds, which XLA keeps."""
    x = jax.lax.optimization_barrier(vals * theta)
    k = x.shape[-1]
    width = 1 << max(k - 1, 0).bit_length()
    if width != k:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - k)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return jax.nn.sigmoid(jax.lax.optimization_barrier(x[..., 0]))


def make_schedule(cfg: DPMRConfig) -> Callable:
    """LR schedule for the sparse face from the shared schedule registry."""
    return schedules.get_schedule_by_name(
        cfg.schedule, cfg.learning_rate,
        warmup_steps=cfg.warmup_steps, total_steps=cfg.total_steps)


# ---------------------------------------------------------------------------
# per-device stage pipeline
# ---------------------------------------------------------------------------


def _device_fwd(cfg, strategy, ctx, kernel_impl,
                cold_loc, hot, hot_ids, ids, vals):
    """Stages distribute+restore: returns (theta (B,K), fwd-state, aux)."""
    flat = ids.reshape(-1)
    with jax.named_scope("dpmr.split_hot"):
        hot_slot, is_hot, cold_ids = hot_sharding.split_hot(flat, hot_ids)

    with jax.named_scope("dpmr.distribute"):
        theta_cold, fwd = strategy.distribute(ctx, cold_loc, cold_ids)
        theta_hot = jnp.where(is_hot, hot[jnp.clip(hot_slot, 0)], 0.0)
        theta = (theta_cold + theta_hot).reshape(ids.shape)
    aux = {"hot_slot": hot_slot, "is_hot": is_hot,
           "overflow": fwd["overflow"]}
    return theta, fwd, aux


def _device_grads(cfg, strategy, ctx, kernel_impl,
                  cold_loc, grads_slot, fwd, aux, strat_loc, stateful,
                  accumulating=False):
    """Reduce stages: per-feature sums delivered to owners + hot psum.

    `strat_loc` is this device's slice of the persistent strategy carry;
    stateful strategies receive it as `fwd["carry"]` and return the
    updated value alongside the gradient. `accumulating=True` marks the
    full-batch grad_step path, where the engine DISCARDS the returned
    carry (many grad_steps feed one update) — it reaches the strategy as
    `fwd["accumulate"]` so lossy strategies whose correctness depends on
    the carry advancing (e.g. topk_reduce) can fall back to an exact
    reduce there."""
    with jax.named_scope("dpmr.reduce"):
        gflat = grads_slot.reshape(-1)
        if stateful:
            grad_cold, strat_new = strategy.reduce(
                ctx, cold_loc, gflat,
                {**fwd, "carry": strat_loc, "accumulate": accumulating})
        else:
            grad_cold = strategy.reduce(ctx, cold_loc, gflat, fwd)
            strat_new = strat_loc

        hot_n = jnp.zeros((cfg.max_hot,), jnp.float32)
        ghot = hot_n.at[jnp.where(aux["is_hot"], aux["hot_slot"],
                                  cfg.max_hot)].add(
            jnp.where(aux["is_hot"], gflat, 0.0), mode="drop")
        grad_hot = jax.lax.psum(ghot, ctx.axes)
    return grad_cold, grad_hot, strat_new


def _metrics(axes, probs, labels, nll, overflow):
    with jax.named_scope("dpmr.metrics"):
        y = labels.astype(jnp.float32)
        pred = (probs >= 0.5).astype(jnp.float32)
        acc = jnp.mean((pred == y).astype(jnp.float32))
        return {
            "loss": jax.lax.pmean(jnp.mean(nll), axes),
            "accuracy": jax.lax.pmean(acc, axes),
            "overflow": jax.lax.psum(overflow, axes),
        }


# ---------------------------------------------------------------------------
# public step builders
# ---------------------------------------------------------------------------


class StepFns(NamedTuple):
    """Typed bundle of compiled DPMR step functions + step geometry.

    Access is attribute-only (`fns.train_step`); the one-release
    deprecated dict-style `fns["train_step"]` has been removed.

    `ctx` is the `StrategyContext` the steps were compiled against —
    feed it to `strategy.bytes_per_device` for the two-tier wire model
    of this exact geometry.

    `train_step` and `apply_update` DONATE their state argument (the
    (F,)-sized table/accumulator buffers alias the outputs instead of
    being copied — `repro.analysis.audit` verifies the aliasing survives
    lowering). Treat the passed-in state as consumed; snapshot with
    `jax.tree.map(jnp.copy, state)` first if you need the old value.
    `grad_step` and `predict` do not donate.
    """

    train_step: Callable     # (state, batch) -> (state, metrics)
    grad_step: Callable      # (state, batch) -> (grad_cold, grad_hot, metrics)
    apply_update: Callable   # (state, grad_cold, grad_hot, lr) -> state
    predict: Callable        # (state, batch) -> probs
    capacity: int            # per-(src,dst) a2a slots
    block_size: int          # feature-table rows per device
    num_shards: int          # P
    strategy: str = "a2a"    # RESOLVED distribution-strategy name (a
    #                          concrete registry entry, never "auto")
    ctx: object = None       # StrategyContext of this compilation


def make_step_fns(cfg: DPMRConfig, mesh, batch_size: int,
                  kernel_impl: str | None = None,
                  cap_factor: float = 4.0) -> StepFns:
    """Build jitted StepFns(train_step, grad_step, apply_update, predict)
    for a GLOBAL batch of `batch_size` samples (sharded over all mesh
    axes).

    `kernel_impl` picks the hot-path lowering ("xla" | "pallas" |
    "pallas_interpret", see repro.kernels.ops.KERNEL_IMPLS); None defers
    to `cfg.kernel_impl`. It reaches the strategies through
    `StrategyContext.kernel_impl` and the map body through
    `ops.sigmoid_grad`, never the collectives — the wire layout is
    impl-independent by construction."""
    # late import: repro.api.engine imports this module
    from repro.api.strategies import get_strategy

    axes = _axes(mesh)
    p = num_shards(mesh)
    f = padded_features(cfg, mesh)
    block = f // p
    assert batch_size % p == 0, (batch_size, p)
    cap = capacity(cfg, batch_size // p, mesh, cap_factor)
    dist = resolve_distribution(cfg, mesh)
    strategy = get_strategy(dist)
    kernel_impl = ops.normalize_impl(
        cfg.kernel_impl if kernel_impl is None else kernel_impl)
    ctx = make_strategy_context(cfg, mesh, cap, kernel_impl=kernel_impl)
    stateful = strategy.init_carry(ctx) is not None
    sched = make_schedule(cfg)

    def _fwd_grads(cold_loc, hot, hot_ids, strat_loc, ids, vals, labels,
                   accumulating=False):
        theta, fwd, aux = _device_fwd(
            cfg, strategy, ctx, kernel_impl,
            cold_loc, hot, hot_ids, ids, vals)
        with jax.named_scope("dpmr.map"):
            grads_slot, probs, nll = ops.sigmoid_grad(
                vals, theta, labels, impl=kernel_impl)
            if cfg.grad_scale == "mean":
                grads_slot = grads_slot / float(batch_size)
        grad_cold, grad_hot, strat_new = _device_grads(
            cfg, strategy, ctx, kernel_impl,
            cold_loc, grads_slot, fwd, aux, strat_loc, stateful,
            accumulating=accumulating)
        return grad_cold, grad_hot, strat_new, _metrics(
            axes, probs, labels, nll, aux["overflow"])

    def train_dev(cold_loc, hot, hot_ids, cold_acc, hot_acc, step,
                  strat_loc, ids, vals, labels):
        grad_cold, grad_hot, strat_new, m = _fwd_grads(
            cold_loc, hot, hot_ids, strat_loc, ids, vals, labels)
        with jax.named_scope("dpmr.optimize"):
            lr = sched(step)
            cold_new, cold_acc = optimize(cfg, cold_loc, cold_acc,
                                          grad_cold, lr)
            hot_new, hot_acc = optimize(cfg, hot, hot_acc, grad_hot, lr)
        return (cold_new, hot_new, hot_ids, cold_acc, hot_acc, step + 1,
                strat_new, m)

    def grad_dev(cold_loc, hot, hot_ids, strat_loc, ids, vals, labels):
        # the carry is read-only here: full-batch fit() accumulates raw
        # gradients across many grad_steps before one update, so per-batch
        # carry mutation would double-count; error feedback advances
        # through train_step (the SGD path) only. accumulating=True tells
        # the strategy (fwd["accumulate"]) so ones that MUST advance the
        # carry to stay correct can take an exact path instead.
        grad_cold, grad_hot, _, m = _fwd_grads(
            cold_loc, hot, hot_ids, strat_loc, ids, vals, labels,
            accumulating=True)
        return grad_cold, grad_hot, m

    def predict_dev(cold_loc, hot, hot_ids, ids, vals):
        theta, _, _ = _device_fwd(cfg, strategy, ctx, kernel_impl,
                                  cold_loc, hot, hot_ids, ids, vals)
        return predict_probs(vals, theta)

    shard = P(axes)
    rep = P()
    smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)

    train_m = smap(train_dev,
                   in_specs=(shard, rep, rep, shard, rep, rep, shard,
                             shard, shard, shard),
                   out_specs=(shard, rep, rep, shard, rep, rep, shard, rep))
    grad_m = smap(grad_dev,
                  in_specs=(shard, rep, rep, shard, shard, shard, shard),
                  out_specs=(shard, rep, rep))
    pred_m = smap(predict_dev,
                  in_specs=(shard, rep, rep, shard, shard),
                  out_specs=shard)

    # the consumed state is DONATED in both updating steps: the (F,)-sized
    # table/accumulator buffers alias their outputs instead of being copied
    # (the analysis auditor checks the aliasing survives lowering). Callers
    # must treat the passed-in state as dead — engine.train_step/fit do.
    # grad_step/predict deliberately do NOT donate: fit() reuses one state
    # across many grad_steps, and predict never updates it.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def train_step(state: DPMRState, batch):
        cold, hot, hot_ids, cold_acc, hot_acc, step, strat, m = train_m(
            state.cold, state.hot, state.hot_ids, state.cold_acc,
            state.hot_acc, state.step, state.strat,
            batch["ids"], batch["vals"], batch["labels"])
        return DPMRState(cold, hot, hot_ids, cold_acc, hot_acc, step,
                         strat), m

    @jax.jit
    def grad_step(state: DPMRState, batch):
        return grad_m(state.cold, state.hot, state.hot_ids, state.strat,
                      batch["ids"], batch["vals"], batch["labels"])

    @functools.partial(jax.jit, donate_argnums=(0,))
    def apply_update(state: DPMRState, grad_cold, grad_hot, lr: float):
        cold, cold_acc = optimize(cfg, state.cold, state.cold_acc,
                                  grad_cold, lr)
        hot, hot_acc = optimize(cfg, state.hot, state.hot_acc, grad_hot, lr)
        return DPMRState(cold, hot, state.hot_ids, cold_acc, hot_acc,
                         state.step + 1, state.strat)

    @jax.jit
    def predict(state: DPMRState, batch):
        return pred_m(state.cold, state.hot, state.hot_ids,
                      batch["ids"], batch["vals"])

    return StepFns(train_step=train_step, grad_step=grad_step,
                   apply_update=apply_update, predict=predict,
                   capacity=cap, block_size=block, num_shards=p,
                   strategy=dist, ctx=ctx)
