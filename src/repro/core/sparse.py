"""Sparse batch format + feature routing math for the DPMR sparse face.

This module is pure per-device math (no collectives), so every function has
a numpy-checkable oracle in the tests. The engine (core.dpmr) wraps these in
shard_map with all_to_all between the routing phases.

Terminology maps to the paper:
  - `route_build`    = invertDocuments + the combiner (duplicate features in a
                       shard are deduplicated before requesting — Algorithm 3's
                       combiner) + the shuffle layout of distributeParameters.
  - `route_return`   = restoreDocuments (responses land request-aligned; the
                       unsort restores the original sample layout).
  - `combine_grads`  = computeGradients' combiner (sum per feature before the
                       reduce-side shuffle).

Feature ownership is contiguous-block: owner(f) = f // block_size, so a sort
by feature id simultaneously groups by owner (monotone) and makes duplicates
adjacent — one sort serves both the shuffle and the combiner.

Batches are padded CSR: ids (B, K) int32 with -1 padding, vals (B, K) f32,
labels (B,) int32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


def _scoped(fn):
    """`fn` traced under a `jax.named_scope` of its own name, so that its
    ops carry the name in the compiled step whichever strategy calls it."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(fn.__name__):
            return fn(*args, **kwargs)

    return scoped


class Routing(NamedTuple):
    """Static-shape routing plan for one device's feature slots."""

    req_ids: jax.Array       # (P, cap) int32, -1 = empty: ids requested per owner
    order: jax.Array         # (n,) argsort-by-id permutation (sorted <- orig)
    owner_s: jax.Array       # (n,) owner of each sorted slot (P = padding)
    pos_s: jax.Array         # (n,) capacity slot of the run containing slot
    keep_s: jax.Array        # (n,) bool: run fits in capacity and is real
    start_idx_s: jax.Array   # (n,) sorted index of the run start for each slot
    overflow: jax.Array      # () int32: dropped unique features (capacity)


@_scoped
def route_build(ids_flat: jax.Array, num_shards: int, block_size: int,
                cap: int) -> Routing:
    """Build the request plan. ids_flat: (n,) int32 with -1 for padding."""
    n = ids_flat.shape[0]
    valid = ids_flat >= 0
    owner = jnp.where(valid, ids_flat // block_size, num_shards)
    # sort by id; padding (-1) would sort first, so remap padding to +inf-ish
    sort_key = jnp.where(valid, ids_flat, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(sort_key, stable=True)
    ids_s = sort_key[order]
    owner_s = owner[order]
    valid_s = valid[order]

    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), ids_s[1:] != ids_s[:-1]]) & valid_s
    u = jnp.cumsum(is_start.astype(jnp.int32))          # runs up to & incl. i
    # owner o's first sorted index
    owner_first = jnp.searchsorted(owner_s, jnp.arange(num_shards),
                                   side="left")
    runs_before_owner = u[jnp.clip(owner_first, 0, n - 1)] - \
        is_start[jnp.clip(owner_first, 0, n - 1)].astype(jnp.int32)
    runs_before_owner = jnp.where(owner_first >= n,
                                  u[-1], runs_before_owner)
    # capacity slot of each element's run, within its owner
    pos_s = (u - 1) - runs_before_owner[jnp.clip(owner_s, 0, num_shards - 1)]
    keep_s = valid_s & (pos_s < cap)

    # scatter unique run-start ids into the request matrix
    req = jnp.full((num_shards, cap), -1, jnp.int32)
    scat_owner = jnp.where(is_start & keep_s, owner_s, num_shards)
    scat_pos = jnp.where(is_start & keep_s, pos_s, 0)
    req = req.at[scat_owner, scat_pos].set(
        jnp.where(is_start & keep_s, ids_s, -1), mode="drop")

    # run-start sorted index for every slot (to copy responses to duplicates)
    start_idx = jnp.where(is_start, jnp.arange(n), -1)
    start_idx_s = jax.lax.cummax(start_idx)

    n_unique = u[-1]
    kept_unique = jnp.sum((is_start & keep_s).astype(jnp.int32))
    overflow = n_unique - kept_unique
    return Routing(req, order, owner_s, pos_s, keep_s, start_idx_s, overflow)


@_scoped
def route_return(routing: Routing, resp: jax.Array) -> jax.Array:
    """Map responses (P, cap) back to the original slot layout (n,).

    resp[o, c] is the value for the c-th unique feature requested from owner
    o. Every duplicate slot copies its run start's response; padding/overflow
    slots get 0.
    """
    n = routing.order.shape[0]
    gathered = resp[jnp.clip(routing.owner_s, 0, resp.shape[0] - 1),
                    routing.pos_s]
    gathered = jnp.where(routing.keep_s, gathered, 0.0)
    # propagate the run-start's value to duplicates; mask padding/overflow
    start_vals = gathered[jnp.clip(routing.start_idx_s, 0, n - 1)]
    vals_sorted = jnp.where(routing.keep_s, start_vals, 0.0)
    out = jnp.zeros((n,), resp.dtype)
    return out.at[routing.order].set(vals_sorted)


@_scoped
def combine_grads(routing: Routing, grads_flat: jax.Array) -> jax.Array:
    """Combiner: sum per-slot grads by feature -> (P, cap) send buffer.

    grads_flat: (n,) in the ORIGINAL slot layout. Output aligns with the
    request matrix (owner, capacity-slot), so the reverse all_to_all delivers
    per-unique-feature sums to owners.
    """
    g_sorted = grads_flat[routing.order]
    g_sorted = jnp.where(routing.keep_s, g_sorted, 0.0)
    send = jnp.zeros((routing.req_ids.shape[0], routing.req_ids.shape[1]),
                     grads_flat.dtype)
    scat_owner = jnp.where(routing.keep_s, routing.owner_s,
                           routing.req_ids.shape[0])
    return send.at[scat_owner, routing.pos_s].add(g_sorted, mode="drop")


@_scoped
def owner_apply(req_ids: jax.Array, table_local: jax.Array,
                base: jax.Array) -> jax.Array:
    """Owner side of distributeParameters: look up requested rows.

    req_ids: (P, cap) global ids (-1 empty); table_local: (rows,);
    base: scalar global id of local row 0. Returns (P, cap) values.
    """
    local = jnp.clip(req_ids - base, 0, table_local.shape[0] - 1)
    vals = table_local[local]
    return jnp.where(req_ids >= 0, vals, 0.0)


@_scoped
def owner_accumulate(req_ids: jax.Array, grads: jax.Array,
                     acc_local: jax.Array, base: jax.Array) -> jax.Array:
    """Owner side of the gradient reduce: scatter-add received sums."""
    local = jnp.where(req_ids >= 0, req_ids - base, acc_local.shape[0])
    return acc_local.at[local.reshape(-1)].add(
        jnp.where(req_ids >= 0, grads, 0.0).reshape(-1), mode="drop")
