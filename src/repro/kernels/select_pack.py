"""Pallas TPU kernel: fused top-k select+pack for the sparsified reduce.

The `topk_reduce` strategy's reverse shuffle (repro/api/strategies.py)
prepares its wire payload with a chain of five XLA ops over the (P, cap)
send buffer: compensate with the error-feedback residual, build a |value|
ranking key, `jax.lax.top_k`, two `take_along_axis` gathers to pack the
(value, id) pairs, and a `where` to bank the losers' residual. Each op is
an HBM round trip over the buffer. This kernel is the whole chain in ONE
pass: each grid step holds destination rows in VMEM, ranks their slots,
and emits the packed pairs plus the residual update without materializing
any intermediate.

Ranking is comparison-matrix style (the same MXU-shaped trick as
segment_sum's equality mask): rank[i] counts slots that beat slot i —
strictly larger key, or equal key at an earlier position. That total
order is exactly `jax.lax.top_k`'s (descending value, ties by position),
so the kernel's selection set and output ORDER are bit-identical to the
reference chain; packing is a one-hot select-and-sum `vals_k[r] =
sum_i comp[i] * [rank[i] == r]` with exactly one live term per output
slot, so no floating-point reassociation happens anywhere. `k` must come from
`repro.optim.compression.topk_count` (the strategy passes it through) so
kernel and wire model cannot disagree.

Layout: a grid step holds a block of up to `ROWS` = 8 destination rows
(all P of them when P <= 8; more are padded to a multiple of 8 with empty
slots), since a (1, cap) block of a (P, cap) array breaks the TPU's
(8, 128) tiling rule. The kernel walks the block's rows one at a time,
and the two row<->column turns the ranking and the packing need are
aligned (128, n) transposes.

The (cap, cap) comparison mask bounds the practical capacity. The v5e
compiler accepts cap = `MAX_CAPACITY` = 4096 at P = 4, k = 1024
(tests/test_chip_compile.py keeps that compile) and refuses 8192 for
scoped VMEM (44 MB asked, 16 MB allowed). Above it the kernel, and a
Pallas `ops.select_pack`, raise; the XLA chain has no bound. Production capacities (4x the mean slots-per-peer,
core.dpmr.capacity) sit below it whenever P >= 4.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# largest per-(src,dst) capacity the row-at-a-time layout is
# compiled for (the v5e compiler accepts it at P = 4, k = cap / 4); the
# kernel and a Pallas ops.select_pack raise past this
MAX_CAPACITY = 4096
ROWS = 8    # destination rows per grid step (a sublane tile)


def _column(row):
    """(1, n) -> (n, 1) through an aligned (128, n) transpose."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _row(col):
    """(n, 1) -> (1, n) through an aligned (n, 128) transpose."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1, :]


def _select_row(send, ids, carry, *, cap: int, k: int):
    """One destination row: (1, cap) send/ids/carry -> (vals_k (1, k),
    ids_k (1, k), residual (1, cap))."""
    valid = ids >= 0
    comp = jnp.where(valid, send.astype(jnp.float32)
                     + carry.astype(jnp.float32), 0.0)
    # dead slots rank below every live one (key -1 < |comp| >= 0); they are
    # picked only when a row has fewer than k live slots, and their id -1
    # no-ops at the owner — same convention as the XLA chain
    key = jnp.where(valid, jnp.abs(comp), -1.0)

    # rank[i] = #{j : key[j] > key[i], or key[j] == key[i] and j < i} —
    # jax.lax.top_k's total order (descending, ties by position), built as
    # a (cap, cap) comparison mask and reduced along the j axis
    key_t = _column(key)                                # key[j] down rows
    jpos = jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 0)
    ipos = jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 1)
    beats = (key_t > key) | ((key_t == key) & (jpos < ipos))
    rank = jnp.sum(beats.astype(jnp.int32), axis=0, keepdims=True)

    selected = rank < k
    # residual update in the same pass: winners flush to zero, losers bank
    # their full compensated value (invalid slots are dropped by the
    # caller's scatter, their content is irrelevant but kept = comp = 0)
    resid = jnp.where(selected & valid, 0.0, comp)

    # pack by rank: ranks are a permutation of 0..cap-1 (the order above is
    # total), so output slot r has exactly ONE source — each sum below
    # moves one winner and adds nothing but zeros to it
    rpos = jax.lax.broadcasted_iota(jnp.int32, (k, cap), 0)
    onehot = rank == rpos                               # (k, cap)
    ids_k = _row(jnp.sum(jnp.where(onehot, ids, 0), axis=1,
                         keepdims=True))                # (1, k)
    vals_k = _row(jnp.sum(jnp.where(onehot, comp, 0.0), axis=1,
                          keepdims=True))
    # rows with < k live slots pack dead slots: emit value 0 for id -1
    return jnp.where(ids_k >= 0, vals_k, 0.0), ids_k, resid


def _kernel(send_ref, ids_ref, carry_ref, vals_ref, idsk_ref, resid_ref,
            *, cap: int, k: int):
    def one_row(r, _):
        row = pl.ds(r, 1)
        vals_k, ids_k, resid = _select_row(
            send_ref[row, :], ids_ref[row, :], carry_ref[row, :],
            cap=cap, k=k)
        vals_ref[row, :] = vals_k.astype(vals_ref.dtype)
        idsk_ref[row, :] = ids_k.astype(idsk_ref.dtype)
        resid_ref[row, :] = resid.astype(resid_ref.dtype)

    jax.lax.fori_loop(0, send_ref.shape[0], one_row, None)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def select_pack(send, ids, carry_slots, *, k: int,
                interpret: bool = False):
    """Fused compensate + rank-by-|magnitude| + pack for one (P, cap)
    destination buffer.

    send:        (P, cap) f32 per-destination gradient sums
    ids:         (P, cap) int32 global feature ids (-1 = empty slot)
    carry_slots: (P, cap) f32 error-feedback residual gathered per slot
                 (`carry[ids]`; the gather/scatter against the (F,) carry
                 stays outside — it is not blockable by destination row)
    k:           slots kept per destination; MUST be
                 `compression.topk_count(cap, frac)`

    Returns (vals_k (P, k) f32, ids_k (P, k) int32, residual (P, cap) f32)
    where residual is the per-slot carry update (0 for selected slots, the
    compensated value for losers), bit-identical to the XLA chain in
    `TopKReduceStrategy.reduce`.
    """
    p, cap = ids.shape
    if cap > MAX_CAPACITY:
        raise ValueError(
            f"select_pack capacity {cap} exceeds MAX_CAPACITY "
            f"{MAX_CAPACITY} (the (cap, cap) ranking mask would outgrow "
            "VMEM); use the XLA chain for this geometry")
    # blocks of ROWS rows (all P rows when P <= ROWS): a block's last two
    # dims are then a multiple of 8 or the array's own, the TPU tiling
    # rule that a (1, cap) block of a (P, cap) array breaks
    rows = p if p <= ROWS else ROWS
    pp = -(-p // rows) * rows
    if pp != p:
        pad = ((0, pp - p), (0, 0))
        send, carry_slots = (jnp.pad(x, pad) for x in (send, carry_slots))
        ids = jnp.pad(ids, pad, constant_values=-1)

    def spec(n):
        return pl.BlockSpec((rows, n), lambda i: (i, 0))

    vals_k, ids_k, resid = pl.pallas_call(
        functools.partial(_kernel, cap=cap, k=k),
        grid=(pp // rows,),
        in_specs=[spec(cap)] * 3,
        out_specs=[spec(k), spec(k), spec(cap)],
        out_shape=[
            jax.ShapeDtypeStruct((pp, k), jnp.float32),
            jax.ShapeDtypeStruct((pp, k), jnp.int32),
            jax.ShapeDtypeStruct((pp, cap), jnp.float32),
        ],
        interpret=interpret,
    )(send, ids, carry_slots)
    return vals_k[:p], ids_k[:p], resid[:p]
