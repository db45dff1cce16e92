"""GPipe-style pipeline parallelism over a `pipe` mesh axis.

Layers are split into S stages; stage s's parameters live on pipe-shard s
(stacked leading dim sharded over `pipe`). Microbatches stream through the
fill/drain schedule — T = M + S - 1 ticks; at tick t stage s computes
microbatch t - s — with stage boundaries crossed by jax.lax.ppermute.
Backward differentiates straight through (ppermute's transpose is the
reverse permute), giving the GPipe fill/drain backward automatically.

This is the optional PP axis for depth-dominated models where FSDP+TP
leaves too little per-device memory; it composes with the data axis (shard
microbatches over `data` inside the stage_fn). The 40-cell grid uses
FSDP+TP(+SP/CP) — PP is exercised by tests/test_pipeline.py and available
via make_pp_mesh.

Bubble fraction = (S-1)/(M+S-1); pick M >= 4S to keep it under 20%.
"""
from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh


def make_pp_mesh(pipe: int, data: int = 1):
    if data == 1:
        return make_mesh((pipe,), ("pipe",))
    return make_mesh((pipe, data), ("pipe", "data"))


def pipeline_apply(stage_params, micro_in, stage_fn: Callable, mesh,
                   axis: str = "pipe"):
    """Run microbatches through the pipeline.

    stage_params: pytree with leading dim = n_stages (sharded over `axis`).
    micro_in:     (M, B_mu, ...) microbatch inputs (replicated over `axis`).
    stage_fn:     (params_slice, x) -> y, same x/y shape (a stage of layers).

    Returns (M, B_mu, ...) outputs (replicated).
    """
    n_stages = int(mesh.shape[axis])
    m = micro_in.shape[0]
    ticks = m + n_stages - 1

    def per_shard(params_local, micro):
        # params_local: (1, ...) this stage's slice;  micro: (M, B, ...)
        p_loc = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        last = n_stages - 1
        perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (clamped; masked when invalid)
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(stage == 0,
                             jax.lax.dynamic_index_in_dim(
                                 micro, mb_idx, 0, keepdims=False),
                             buf)
            y = stage_fn(p_loc, x_in)
            # drain: last stage writes its result for microbatch t-(S-1)
            out_idx = jnp.clip(t - last, 0, m - 1)
            valid = (t >= last) & (t - last < m)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid & (stage == last), y,
                                jax.lax.dynamic_index_in_dim(
                                    outs, out_idx, 0, keepdims=False)),
                out_idx, 0)
            # boundary transfer to the next stage
            nxt = jax.lax.ppermute(y, axis, perm_fwd)
            return (nxt, upd), None

        buf0 = jnp.zeros_like(micro[0])
        outs0 = jnp.zeros_like(micro)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(ticks))
        # only the LAST stage holds real outputs; broadcast them to all
        # pipe shards so the result is replicated (psum of masked outs)
        outs = jax.lax.psum(
            jnp.where(stage == last, outs, jnp.zeros_like(outs)), axis)
        return outs

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P(),
        axis_names={axis}, check_vma=False)
    return fn(stage_params, micro_in)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
