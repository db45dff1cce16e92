"""Zipf hot-feature handling (paper §4, adapted).

On Hadoop, a head feature's `feature -> sample` line spans ~20 HDFS blocks
and serializes one reducer; the paper splits it into N sub-features. In SPMD
the same skew shows up as per-owner request-buffer overflow (the a2a
capacity). The adaptation: features above a frequency threshold are
REPLICATED on every device (their parameters travel with the program, their
gradients reduce over the full mesh with one psum), and only the Zipf tail
goes through the a2a routing — which is near-uniform by hashing, so a small
capacity factor suffices. `select_hot` is the initParameters-time frequency
statistic the paper passes to its sharding mappers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

INT_MAX = jnp.iinfo(jnp.int32).max


def select_hot(ids: np.ndarray, threshold: float, max_hot: int
               ) -> np.ndarray:
    """Pick features with frequency above `threshold`, capped at max_hot.

    ids: any shape, -1 = padding. Counted on the host over the ids present
    (no (F,) histogram anywhere): the `max_hot` most frequent eligible
    features, ties to the lower id. Returns (max_hot,) int32 sorted
    ascending (the serving mirror binary-searches it), padded with
    INT_MAX, an id no feature has, so that `split_hot`'s compare matches
    no padding entry.
    """
    ids = np.asarray(ids).reshape(-1)
    uniq, counts = np.unique(ids[ids >= 0], return_counts=True)
    freq = counts.astype(np.float32) / np.float32(max(int(counts.sum()), 1))
    keep = freq >= np.float32(threshold)
    uniq, counts = uniq[keep], counts[keep]
    top = uniq[np.lexsort((uniq, -counts))[:max_hot]]
    out = np.full((max_hot,), int(INT_MAX), np.int32)
    out[:len(top)] = np.sort(top)
    return out


def split_hot(ids_flat: jax.Array, hot_ids: jax.Array
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partition flat ids into hot/cold.

    hot_ids: as `select_hot` returns them, INT_MAX padded. Each id's slot
    is the least index of `hot_ids` that holds it (searchsorted's side
    left where `hot_ids` is sorted): one all-pairs compare of n ids with
    H hot ids, reduced without storing the (H, n) compare. n*H compares
    in one fusion cost less on the chip than searchsorted's log2(H)
    gathers of every id, up to H = 65,536 at least (PERF.md).

    Returns (hot_slot (n,) int32 index into hot_ids or -1,
             is_hot (n,) bool,
             cold_ids (n,) int32 with hot & padding replaced by -1).
    """
    h = hot_ids.shape[0]
    j = jnp.arange(h, dtype=jnp.int32)[:, None]
    pos = jnp.min(jnp.where(hot_ids[:, None] == ids_flat[None, :], j, h),
                  axis=0)
    is_hot = (pos < h) & (ids_flat >= 0)
    hot_slot = jnp.where(is_hot, pos, -1)
    cold_ids = jnp.where(is_hot | (ids_flat < 0), -1, ids_flat)
    return hot_slot, is_hot, cold_ids


def load_imbalance(ids_flat: jax.Array, num_shards: int, block_size: int
                   ) -> jax.Array:
    """max/mean owner load for this device's cold ids (skew diagnostic)."""
    owner = jnp.where(ids_flat >= 0, ids_flat // block_size, num_shards)
    counts = jnp.zeros((num_shards,), jnp.int32).at[owner].add(
        1, mode="drop")
    mean = jnp.maximum(jnp.mean(counts.astype(jnp.float32)), 1e-6)
    return jnp.max(counts).astype(jnp.float32) / mean
