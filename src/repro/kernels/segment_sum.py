"""Pallas TPU kernel: segment-sum over SORTED feature ids (the DPMR reduce
combiner, Algorithm 6's combiner/reducer adapted to the MXU).

On Hadoop the combiner is a hash-aggregation; scatter-add is the XLA
equivalent but lowers to serialized scatter on TPU. The TPU-native trick:
with ids sorted, per-run sums are a *masked matmul* —
    run_total[i] = sum_j grads[j] * (ids[j] == ids[i])
computed blockwise on the MXU with an (Nb x Nb) equality mask, plus a
carry between consecutive blocks (grid steps run sequentially on a TPU
core, so scratch persists across them).

Output convention (== ref.segment_sum_sorted_ref): each run's total is
emitted at the run's LAST slot; all other slots are 0. Emitting at the end
makes the carry one-directional: a block adds the carried partial of a run
that began earlier, and forwards its own trailing partial.

Layout: the (N,) vectors are viewed as (N/Nb, 1, Nb) rows, so every
block's last two dims equal the array's (the TPU tiling rule that 1-D
blocks break). The kernel turns its id row into a column with one
(128, Nb) transpose, and the equality mask is then a plain broadcast of
the column against the row. Per element the wrapper
also supplies the NEXT id (is this slot a run end?), and per block, as a
scalar-prefetch operand in SMEM, the id of the run that continues into it
from the previous block (-1 if none). N that is not a multiple of the
block is padded with id -1 (padding sorts last anyway) and sliced back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(carry_ids_ref, ids_ref, nxt_ref, grads_ref, out_ref,
            carry_sum_ref):
    i = pl.program_id(0)
    ids = ids_ref[...]                                  # (1, Nb)
    valid = ids >= 0
    g = jnp.where(valid, grads_ref[...].astype(jnp.float32), 0.0)

    @pl.when(i == 0)
    def _init():
        carry_sum_ref[...] = jnp.zeros_like(carry_sum_ref)

    # eq[j, i] = ids[j] == ids[i] -> run totals as one (1,Nb)x(Nb,Nb) matmul
    # row -> column through an aligned (128, Nb) transpose
    idc = jnp.broadcast_to(ids, (128, ids.shape[1])).T[:, :1]   # (Nb, 1)
    eq = ((idc == ids) & (idc >= 0)).astype(jnp.float32)
    totals = jnp.dot(g, eq, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    # the run continuing from previous blocks gets their partial sum
    cont = (ids == carry_ids_ref[i]) & valid
    totals = totals + jnp.where(cont, carry_sum_ref[...], 0.0)

    is_end = valid & (ids != nxt_ref[...])
    out_ref[...] = jnp.where(is_end, totals, 0.0).astype(out_ref.dtype)
    # the trailing partial: read by the next block only if its run goes on
    carry_sum_ref[...] = totals[:, -1:]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def segment_sum_sorted(ids, grads, *, block: int = 256,
                       interpret: bool = False):
    """ids: (N,) int32 sorted ascending (negatives = padding, sorted LAST by
    the caller); grads: (N,) f32. Returns (N,) f32 with each run's total at
    the run's last slot, 0 elsewhere."""
    n = ids.shape[0]
    nb = min(block, n)
    npad = -(-n // nb) * nb
    if npad != n:
        ids = jnp.pad(ids, (0, npad - n), constant_values=-1)
        grads = jnp.pad(grads, (0, npad - n))
    g = npad // nb
    nxt = jnp.concatenate([ids[1:], jnp.full((1,), -2, ids.dtype)])
    # per block: the id of a run that crosses into it from the block before
    first, prev_last = ids[nb::nb], ids[nb - 1:-1:nb]
    carry_ids = jnp.concatenate([
        jnp.full((1,), -1, ids.dtype),
        jnp.where((prev_last >= 0) & (prev_last == first), first, -1)])
    row = pl.BlockSpec((None, 1, nb), lambda i, c: (i, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g,),
            in_specs=[row, row, row],
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, 1, nb), jnp.float32),
        interpret=interpret,
    )(carry_ids, ids.reshape(g, 1, nb), nxt.reshape(g, 1, nb),
      grads.reshape(g, 1, nb))
    return out.reshape(npad)[:n]
