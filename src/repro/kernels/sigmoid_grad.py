"""Pallas TPU kernel: fused DPMR inference + per-feature gradient.

The computeGradients map body (paper Algorithm 6): per sufficient sample,
logit = <vals, theta>, p = sigmoid(logit), grad slot = vals * (p - y), plus
the per-sample NLL. One pass over the (B, K) sufficient-sample block held in
VMEM — on HBM-bound sparse workloads this is a single read of vals/theta and
a single write of grads (the jnp version materializes logits/probs between
HBM round trips).

Block layout: grid over batch tiles; each program holds a (Bb, K) tile of
vals/theta in VMEM (K is the padded features-per-sample, typically 64-256,
so a 256 x 256 f32 tile is 256 KB — well under VMEM). The per-sample
vectors (labels in, probs and NLL out) travel as (B, 1) columns: the logit
is a lane reduction over K, so a sample's scalar lives on its own sublane,
and a (Bb, 1) block satisfies the TPU tiling rule (Bb a multiple of 8, the
last dim equal to the array's) where a 1-D (Bb,) block does not. A batch
that is not a multiple of the block is zero-padded up to one and sliced
back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(vals_ref, theta_ref, labels_ref, grads_ref, probs_ref, nll_ref):
    vals = vals_ref[...].astype(jnp.float32)
    theta = theta_ref[...].astype(jnp.float32)
    y = labels_ref[...].astype(jnp.float32)                 # (Bb, 1)
    logits = jnp.sum(vals * theta, axis=-1, keepdims=True)  # (Bb, 1)
    probs = jax.nn.sigmoid(logits)
    grads_ref[...] = (vals * (probs - y)).astype(grads_ref.dtype)
    probs_ref[...] = probs.astype(probs_ref.dtype)
    # nll = -y*log_sigmoid(z) - (1-y)*log_sigmoid(-z)
    nll = -(y * jax.nn.log_sigmoid(logits)
            + (1.0 - y) * jax.nn.log_sigmoid(-logits))
    nll_ref[...] = nll.astype(nll_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def sigmoid_grad(vals, theta, labels, *, block_b: int = 256,
                 interpret: bool = False):
    """vals, theta: (B, K); labels: (B,). Returns (grads, probs, nll)."""
    b, k = vals.shape
    bb = min(block_b, -(-b // 8) * 8)
    bp = -(-b // bb) * bb
    labels = labels.reshape(b, 1)
    if bp != b:
        pad = ((0, bp - b), (0, 0))
        vals, theta, labels = (jnp.pad(x, pad) for x in
                               (vals, theta, labels))
    tile = pl.BlockSpec((bb, k), lambda i: (i, 0))
    col = pl.BlockSpec((bb, 1), lambda i: (i, 0))
    grads, probs, nll = pl.pallas_call(
        _kernel,
        grid=(bp // bb,),
        in_specs=[tile, tile, col],
        out_specs=[tile, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(vals, theta, labels)
    return grads[:b], probs[:b, 0], nll[:b, 0]
