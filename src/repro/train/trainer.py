"""Train-step builder: loss, grad accumulation, clipping, sharded optimizer.

The step is pjit-auto over the mesh; parameters carry DPMR-dense (FSDP)
shardings from their logical axes, so XLA materializes the per-layer
all-gather (distributeParameters) inside the layer scan and reduce-scatters
gradients (the feature reduce) in backward — see core/fsdp.py for the
explicit equivalence proof.

Cross-pod gradient compression (ParallelConfig.compress_pod_grads): grads
are computed per pod under shard_map(axis_names={'pod'}) — GSPMD still
handles data/model inside — then reduced across pods with error-feedback
int8 (optim/compression.py).
"""
from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig
from repro.models import common
from repro.optim import compression, optimizers, schedules
from repro.sharding import Annotated

AUX_COEF = 0.01      # MoE load-balance loss weight


def state_defs(spec, cfg: ModelConfig, train_cfg: TrainConfig,
               parallel: ParallelConfig) -> dict:
    """Annotated defs for the full train state (params + opt + step)."""
    pd = spec.defs(cfg)
    opt = optimizers.get_optimizer(train_cfg.optimizer)
    defs = {
        "params": pd,
        "opt": opt.init_defs(pd, cfg.opt_dtype),
        "step": Annotated((), "int32", ()),
    }
    if parallel.compress_pod_grads:
        defs["err"] = jax.tree.map(
            lambda a: Annotated(a.shape, "float32", a.logical), pd,
            is_leaf=lambda x: isinstance(x, Annotated))
    return defs


def init_state(spec, cfg: ModelConfig, train_cfg: TrainConfig,
               parallel: ParallelConfig, key) -> dict:
    pd = spec.defs(cfg)
    params = shd.init_from_defs(pd, key, scale_fn=common.embed_init_scale)
    opt = optimizers.get_optimizer(train_cfg.optimizer)
    state = {"params": params, "opt": opt.init(params, cfg.opt_dtype),
             "step": jnp.zeros((), jnp.int32)}
    if parallel.compress_pod_grads:
        state["err"] = compression.init_error_state(params)
    return state


def make_loss_fn(spec, cfg: ModelConfig, parallel: ParallelConfig):
    def loss_fn(params, batch):
        logits, aux = spec.forward(params, batch, cfg, parallel)
        nll = common.cross_entropy(logits, batch["labels"])
        loss = nll + AUX_COEF * aux
        return loss, {"nll": nll, "aux": aux}

    return loss_fn


def _split_micro(batch: dict, k: int) -> dict:
    def sp(x):
        b = x.shape[0]
        assert b % k == 0, (b, k)
        return x.reshape((k, b // k) + x.shape[1:])

    return jax.tree.map(sp, batch)


def make_train_step(spec, cfg: ModelConfig, train_cfg: TrainConfig,
                    parallel: ParallelConfig, mesh) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics)."""
    loss_fn = make_loss_fn(spec, cfg, parallel)
    opt = optimizers.get_optimizer(train_cfg.optimizer)
    sched = schedules.get_schedule(train_cfg)
    k = max(parallel.microbatches, 1)
    has_pod = "pod" in mesh.axis_names
    compress = parallel.compress_pod_grads and has_pod

    def grads_of(params, batch):
        if k == 1:
            (loss, m), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, loss, m
        micro = _split_micro(batch, k)

        def body(carry, mb):
            g_acc, l_acc, a_acc = carry
            (loss, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(a.dtype), g_acc, g)
            return (g_acc, l_acc + loss, a_acc + m["aux"]), None

        adt = jnp.dtype(parallel.accum_dtype)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, adt), params)
        (grads, loss, aux), _ = jax.lax.scan(
            body, (g0, jnp.float32(0.0), jnp.float32(0.0)), micro)
        grads = jax.tree.map(lambda g: g / k, grads)
        return grads, loss / k, {"nll": loss / k, "aux": aux / k}

    def apply(state, grads, loss, m):
        grads, gnorm = optimizers.clip_by_global_norm(
            grads, train_cfg.grad_clip)
        lr = sched(state["step"])
        params, opt_state = opt.update(grads, state["opt"], state["params"],
                                       lr, train_cfg)
        new = dict(state, params=params, opt=opt_state,
                   step=state["step"] + 1)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr, **m}
        return new, metrics

    if not compress:
        def train_step(state, batch):
            grads, loss, m = grads_of(state["params"], batch)
            return apply(state, grads, loss, m)
    else:
        def pod_body(params, err, batch):
            grads, loss, m = grads_of(params, batch)
            g_hat, new_err = compression.compress_tree_psum(
                grads, err, "pod")
            loss = jax.lax.pmean(loss, "pod")
            m = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), m)
            return g_hat, new_err, loss, m

        def train_step(state, batch):
            pspec = jax.tree.map(lambda _: P(), state["params"])
            bspec = jax.tree.map(lambda _: P("pod"), batch)
            body = jax.shard_map(
                pod_body, mesh=mesh,
                in_specs=(pspec, pspec, bspec),
                out_specs=(pspec, pspec, P(), jax.tree.map(lambda _: P(),
                                                           {"nll": 0,
                                                            "aux": 0})),
                axis_names={"pod"}, check_vma=False)
            g_hat, new_err, loss, m = body(state["params"], state["err"],
                                           batch)
            state = dict(state, err=new_err)
            return apply(state, g_hat, loss, m)

    return train_step


def shardings_for_state(defs, mesh):
    return shd.tree_shardings(defs, mesh)


def batch_shardings(batch_defs, mesh):
    return shd.tree_shardings(batch_defs, mesh)
