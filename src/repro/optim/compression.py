"""Gradient compression for the cross-pod (DCN) reduction.

The `pod` mesh axis crosses data-center network, ~10x slower than ICI; the
classic mitigation is compressed all-reduce with error feedback (1-bit
Adam / EF-SGD lineage). We implement int8 block-quantized all-reduce:

    q = round((g - e) / scale),  scale = max|g - e| / 127 per block
    g_hat = psum(q * scale) / n_pods
    e'    = (g - e) - dequant(q)          (error feedback, carried)

Used by the dense trainer via shard_map over ONLY the `pod` axis
(`axis_names={'pod'}`), leaving data/model sharding to GSPMD inside, and by
the sparse face's `compressed_reduce` distribution strategy
(repro/api/strategies.py), which quantizes the dense gradient reduce with
the same `quantize`/`dequantize` primitives and carries its error feedback
in `DPMRState.strat`. Wire-bytes drop 4x (f32->int8); error feedback keeps
SGD/Adam convergence (validated against uncompressed training in
tests/test_multidevice.py and benchmarks/strategy_hierarchy.py).

The top-k selection helpers (`topk_count`, `topk_select`, `topk_mask`)
live here too: the `topk_reduce` strategy builds its sparsified reverse
shuffle — and its wire model's k — out of exactly these primitives, with
the same error-feedback discipline as the quantizer above.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BLOCK = 2048


def _quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-block symmetric int8. x: (N,) f32 (N % BLOCK == 0 after pad)."""
    xb = x.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(xb), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequantize(q: jax.Array, scale: jax.Array, n: int) -> jax.Array:
    return (q.astype(jnp.float32) * scale).reshape(-1)[:n]


# public names of the block (de)quantizer — the compressed_reduce strategy
# builds its wire format out of exactly these primitives
quantize = _quantize
dequantize = _dequantize


def topk_count(n: int, frac: float) -> int:
    """k for a top-`frac` selection out of `n` slots: ceil(frac * n),
    clamped to [1, n]. Shared by the topk_reduce strategy's reduce path and
    its `bytes_per_device` wire model so the two can never disagree."""
    return int(min(n, max(1, math.ceil(frac * n))))


def topk_select(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k selection along the last axis: `(indices, mask)` of the k
    largest entries per row (ties broken by position, exactly
    `jax.lax.top_k`'s order). `x` is the selection key — pass magnitudes,
    with invalid slots already pushed below every valid one. One top_k +
    one O(rows * k) scatter; no (rows, k, n) intermediate. The
    `topk_reduce` strategy gathers its wire payload with `indices` and
    updates its error-feedback residual with `mask`, so send and residual
    can never disagree about what was selected."""
    n = x.shape[-1]
    flat = x.reshape(-1, n)
    idx = jax.lax.top_k(flat, k)[1]                    # (rows, k)
    rows = jnp.arange(flat.shape[0])[:, None]
    mask = jnp.zeros(flat.shape, jnp.bool_).at[rows, idx].set(True)
    return (idx.reshape(x.shape[:-1] + (k,)), mask.reshape(x.shape))


def topk_mask(x: jax.Array, k: int) -> jax.Array:
    """The boolean-mask half of `topk_select` (exactly k True per row)."""
    return topk_select(x, k)[1]


def compress_psum(g: jax.Array, err: jax.Array, axis: str
                  ) -> tuple[jax.Array, jax.Array]:
    """Error-feedback int8 psum over `axis`. g, err: same shape.

    Returns (mean-reduced g_hat, new error state).
    """
    shape = g.shape
    n = g.size
    pad = (-n) % BLOCK
    flat = jnp.pad(g.reshape(-1).astype(jnp.float32) +
                   err.reshape(-1).astype(jnp.float32), (0, pad))
    q, scale = _quantize(flat)
    local_deq = _dequantize(q, scale, n)
    new_err = (flat[:n] - local_deq).reshape(shape)
    # put int8 on the wire: all_gather(q) + all_gather(scale), dequantize and
    # sum locally — for small pod counts this moves ~4x fewer bytes across
    # DCN than an f32 ring all-reduce
    q_all = jax.lax.all_gather(q, axis)               # (pods, blocks, BLOCK)
    s_all = jax.lax.all_gather(scale, axis)           # (pods, blocks, 1)
    deq = (q_all.astype(jnp.float32) * s_all).sum(0).reshape(-1)[:n]
    npods = jax.lax.axis_size(axis)
    return deq.reshape(shape) / npods, new_err


def compress_tree_psum(grads, err_tree, axis: str):
    """Apply compress_psum leaf-wise."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err_tree)
    outs = [compress_psum(g, e, axis) for g, e in zip(flat_g, flat_e, strict=True)]
    g_hat = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_err = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return g_hat, new_err


def init_error_state(params):
    """Zero error-feedback buffers, sharded like params."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def wire_bytes(params) -> tuple[int, int]:
    """(uncompressed, compressed) bytes per cross-pod reduction."""
    n = sum(p.size for p in jax.tree.leaves(params))
    raw = n * 4
    comp = n * 1 + (n // BLOCK + 1) * 4
    return raw, comp
