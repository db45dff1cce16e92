"""Plain reference of the DPMR update, independent of routing and of the
program's optimizer and schedule code.

The engine (`core/dpmr.py`) shards the table, splits hot from cold
features, routes ids to owners and back, and sums gradients on the owners.
None of that happens here: theta is ONE dense (F,) table, a batch gathers
`theta[ids]`, applies the sigmoid, and scatter-adds its per-slot gradients
into a dense (F,) gradient, and the sgd or adagrad update is written out
below at the configuration's constant learning rate. The same steps on the
same batches must give the engine's losses and parameters
(`engine_table`), up to float32 summation order.

    theta, acc, losses = sgd_steps(cfg, batches)         # == fit_sgd, jnp f32
    theta = gd_iterations(cfg, batches, iterations=2)    # == fit, numpy
"""
from __future__ import annotations

from collections.abc import Iterable
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DPMRConfig


def _check(cfg: DPMRConfig) -> None:
    if cfg.optimizer not in ("sgd", "adagrad"):
        raise ValueError(f"the reference has sgd and adagrad, not "
                         f"{cfg.optimizer!r}")
    if cfg.schedule != "constant":
        raise ValueError(f"the reference has the constant schedule, not "
                         f"{cfg.schedule!r}")


def _update(cfg: DPMRConfig, theta, acc, grad, rsqrt):
    """theta - lr * g (sgd); adagrad: acc += g^2, theta - lr * g *
    rsqrt(acc + eps)."""
    lr = cfg.learning_rate
    if cfg.optimizer == "sgd":
        return theta - lr * grad, acc
    acc = acc + grad * grad
    return theta - lr * (grad * rsqrt(acc + cfg.adagrad_eps)), acc


def batch_grad(theta, ids, vals, labels, grad_scale: str = "mean"):
    """Dense (F,) float32 gradient and mean NLL of one batch.

    ids, vals: (B, K) with id -1 at padded slots; labels: (B,) in {0, 1}."""
    f = theta.shape[0]
    valid = ids >= 0
    th = jnp.where(valid, theta[jnp.clip(ids, 0, f - 1)], 0.0)
    logits = jnp.sum(vals * th, axis=-1)
    y = labels.astype(jnp.float32)
    g = vals * (jax.nn.sigmoid(logits) - y)[:, None]
    if grad_scale == "mean":
        g = g / float(ids.shape[0])
    grad = jnp.zeros_like(theta).at[jnp.where(valid, ids, f)].add(
        jnp.where(valid, g, 0.0), mode="drop")
    nll = -(y * jax.nn.log_sigmoid(logits)
            + (1 - y) * jax.nn.log_sigmoid(-logits))
    return grad, jnp.mean(nll)


def sgd_steps(cfg: DPMRConfig, batches: Iterable[dict],
              num_features: int | None = None
              ) -> tuple[jax.Array, jax.Array, list[float]]:
    """Minibatch updates in float32 `jnp`, one per batch, from a zero table
    (the engine's `fit_sgd` from a fresh state). Returns (theta, optimizer
    accumulator, per-step losses)."""
    _check(cfg)
    f = cfg.num_features if num_features is None else num_features

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(theta, acc, ids, vals, labels):
        grad, loss = batch_grad(theta, ids, vals, labels, cfg.grad_scale)
        theta, acc = _update(cfg, theta, acc, grad, jax.lax.rsqrt)
        return theta, acc, loss

    theta = jnp.zeros((f,), jnp.float32)
    acc = jnp.zeros((f,), jnp.float32)
    losses = []
    for b in batches:
        theta, acc, loss = step(theta, acc, *(np.asarray(b[k]) for k in
                                              ("ids", "vals", "labels")))
        losses.append(float(loss))
    return theta, acc, losses


def gd_iterations(cfg: DPMRConfig, batches: list[dict], iterations: int,
                  num_features: int | None = None) -> np.ndarray:
    """Full-batch gradient descent in numpy from a zero table (the engine's
    `fit`): each iteration sums every slot's gradient in float64, averages
    over the batches and makes one float32 update."""
    _check(cfg)
    f = cfg.num_features if num_features is None else num_features
    theta = np.zeros((f,), np.float32)
    acc = np.zeros((f,), np.float32)
    for _ in range(iterations):
        total = np.zeros((f,), np.float64)
        for b in batches:
            ids, vals = np.asarray(b["ids"]), np.asarray(b["vals"])
            y = np.asarray(b["labels"]).astype(np.float32)
            valid = ids >= 0
            th = np.where(valid, theta[np.clip(ids, 0, f - 1)], 0.0)
            logits = (vals * th).sum(-1)
            g = vals * (1 / (1 + np.exp(-logits)) - y)[:, None]
            if cfg.grad_scale == "mean":
                g = g / ids.shape[0]
            np.add.at(total, np.clip(ids, 0, f - 1), np.where(valid, g, 0.0))
        theta, acc = _update(cfg, theta, acc,
                             (total / len(batches)).astype(np.float32),
                             lambda x: 1 / np.sqrt(x))
    return theta


def engine_table(state) -> np.ndarray:
    """The engine's parameters as one dense host (F,) table: the cold
    shards with each hot feature's value written back at its id."""
    theta = np.array(jax.device_get(state.cold), np.float32)
    hot_ids = np.asarray(jax.device_get(state.hot_ids))
    real = hot_ids < np.iinfo(np.int32).max
    theta[hot_ids[real]] = np.asarray(jax.device_get(state.hot))[real]
    return theta
