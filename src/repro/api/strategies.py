"""Pluggable parameter-distribution strategies for the DPMR sparse engine.

The paper's distributeParameters / gradient-reduce shuffle is one point in a
design space (its §5 comparison against broadcast-style distribution is the
central efficiency claim). This module makes that axis a first-class,
registry-backed component: a `DistributionStrategy` implements the two
collective-bearing stages of the per-device pipeline, and `core.dpmr` asks
the registry for whichever one `DPMRConfig.distribution` names.

The mesh is two-tier: `ctx.inner_axes` (ICI, fast) and `ctx.outer_axes`
(DCN, ~10x slower; the `pod` axis when present). Every strategy's
`bytes_per_device` wire model is therefore two-tier too — it returns a
`WireBytes(inner, outer)` counting the bytes a device RECEIVES per step,
classified by whether the sender sits in the same inner group (ICI) or in
another outer group (DCN). A device's own chunk never leaves the chip and
is never counted — `repro.analysis.audit` cross-checks every model against
the jaxpr-extracted collectives under exactly this convention.

Built-ins (P = shards, Pi = inner shards, cap = a2a capacity, |F|/P =
block rows per device):

  a2a              the paper's shuffle: route_build + all_to_all of
                   requested rows, reverse all_to_all of per-feature
                   gradient sums. Total 3*(P-1)*cap*4, |F|-independent;
                   the (P-Pi) buckets from other pods cross DCN.
  allgather        the ship-the-table strawman: all_gather the full table,
                   dense scatter-add + psum_scatter reduce.
                   Total ~2*|F|*4, of which the blocks owned by other pods
                   (2*(|F|/P)*(P-Pi)*4) cross DCN.
  psum_scatter     hybrid: sparse a2a shuffle forward, dense psum_scatter
                   reduce. 2*(P-1)*cap*4 + (|F|/P)*(P-1)*4.
  hier_a2a         two-level exchange: each device mirrors its inner-peer
                   blocks across pods (all_gather over `pod`), the sparse
                   all-to-all then runs ONLY inside the fast inner axes,
                   and the reduce crosses DCN once with the already-reduced
                   per-pod partials (psum_scatter of the owner blocks).
                   DCN bytes = 2*(|F|/P)*(Po-1)*4, independent of the batch
                   — strictly below flat a2a's 3*(P-Pi)*cap*4 whenever the
                   per-device table block is smaller than the shuffled
                   request volume (the paper's huge-batch regime).
  compressed_reduce sparse a2a forward; the dense reduce puts int8 on the
                   wire (optim/compression.py block quantization) with
                   error feedback carried in `DPMRState.strat` and
                   persisted by engine save()/restore(). ~4x fewer reduce
                   bytes than psum_scatter at f32.
  topk_reduce      sparse a2a forward; the reverse shuffle sends only the
                   k = ceil(topk_frac*cap) largest-|g| slots per
                   destination as (value, id) pairs, the rest feed a
                   per-device error-feedback residual in `DPMRState.strat`.
                   Reduce bytes drop cap -> 2k on both tiers.
  overlap_a2a      a2a with every exchange split into micro-chunk
                   collectives XLA can dispatch asynchronously and overlap
                   with the step's compute. Bit-identical to a2a; same
                   wire bytes.
  hier_a2a+topk    per-tier composition (`ComposedStrategy`): hier_a2a's
                   exact exchange on ICI, a top-k sparsified reduce on the
                   DCN leg only — k = ceil(topk_frac*(|F|/P)) (value, row)
                   pairs per pod pair, error feedback in `DPMRState.strat`.
  hier_a2a+int8    same composition with the DCN partials crossing as int8
                   + per-block f32 scales (compressed_reduce's scheme on
                   the outer tier only).

All exact strategies produce identical parameters when capacity does not
overflow (tested in tests/test_dpmr.py) — `overlap_a2a` bit-identically so;
`compressed_reduce` / `topk_reduce` track them to within quantization /
sparsification error (convergence parity is benchmarked in
benchmarks/strategy_hierarchy.py and benchmarks/strategy_overlap.py). They
differ in wire bytes per tier and in how capacity-overflowed features
degrade.

Third parties extend the seam with either

    @register_strategy("my_strategy")
    class MyStrategy(DistributionStrategy): ...

or `register_strategy("name", instance)` — the authoring contract (method
semantics, the two-tier wire model, persistent carry state) is documented
in docs/strategies.md with a runnable example.

Every method runs INSIDE shard_map: `cold_loc` is this device's block of the
feature table and collectives run over `ctx.axes` (or a tier subset).
"""
from __future__ import annotations

import copy
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import sparse
from repro.kernels import ops
from repro.optim import compression


class WireBytes(NamedTuple):
    """Per-device per-step wire cost, split by mesh tier.

    `inner` travels the fast intra-pod interconnect (ICI); `outer` crosses
    the slow inter-pod network (DCN). `total` is the legacy single number.
    """

    inner: int
    outer: int

    @property
    def total(self) -> int:
        return self.inner + self.outer


class StrategyContext(NamedTuple):
    """Static per-step geometry handed to every strategy method.

    `axes` are ALL mesh axes the pipeline is manual over; they factor into
    `outer_axes` (the DCN-crossing tier, `("pod",)` on multi-pod meshes,
    `()` otherwise) followed by `inner_axes` (everything else, ICI). The
    outer axes are always a LEADING prefix of `axes` (launch.mesh.tier_axes
    enforces this), so the linear device index over `axes` decomposes as
    `outer_index * inner_shards + inner_index`.

    Analytic callers (benchmarks, dry-runs) may leave the axis names empty
    and set only the shard counts; only the collectives need real names.
    """

    axes: tuple[str, ...]    # mesh axis names the pipeline is manual over
    num_shards: int          # P = product of mesh axis sizes
    block_size: int          # rows of the feature table per device
    capacity: int            # per-(src,dst) a2a slots for cold features
    inner_axes: tuple[str, ...] = ()   # fast tier (ICI); () = all of `axes`
    outer_axes: tuple[str, ...] = ()   # slow tier (DCN); () = single tier
    outer_shards: int = 1    # Po = product of outer axis sizes
    topk_frac: float = 0.25  # topk_reduce: kept fraction of the capacity
    #                          slots (k = ceil(topk_frac * capacity));
    #                          threaded from DPMRConfig.topk_frac by
    #                          core.dpmr.make_strategy_context
    kernel_impl: str = "xla"  # lowering of the routing hot path
    #                          (repro.kernels.ops.KERNEL_IMPLS): "xla" =
    #                          the reference jnp chain, "pallas"/"pallas_
    #                          interpret" = the fused kernels. Threaded
    #                          from DPMRConfig.kernel_impl by
    #                          core.dpmr.make_step_fns; strategies consult
    #                          it through kernels.ops dispatchers only, so
    #                          collectives (and the audited wire model)
    #                          are identical across impls.

    @property
    def inner_shards(self) -> int:
        """Pi = devices per pod (over the fast tier)."""
        return self.num_shards // max(self.outer_shards, 1)


class DistributionStrategy:
    """Interface for the distributeParameters / reduce pair of stages.

    `distribute` returns the per-slot cold parameters plus an opaque
    forward-state dict that the engine threads into `reduce`; `overflow`
    must be a scalar int32 in that dict (0 when the strategy cannot drop).

    A strategy may carry persistent per-device state across steps (e.g.
    compression error feedback): override `init_carry` to return its
    zero value — a 1-D f32 array of static length. The engine then stores
    it in `DPMRState.strat` (checkpointed by save()/restore()), passes the
    current value to `reduce` as `fwd["carry"]`, and expects `reduce` to
    return `(grad_cold, new_carry)` instead of the bare gradient.
    """

    name: str = "base"

    def distribute(self, ctx: StrategyContext, cold_loc: jax.Array,
                   cold_ids: jax.Array) -> tuple[jax.Array, dict]:
        raise NotImplementedError

    def reduce(self, ctx: StrategyContext, cold_loc: jax.Array,
               grads_flat: jax.Array, fwd: dict) -> jax.Array:
        raise NotImplementedError

    def init_carry(self, ctx: StrategyContext) -> jax.Array | None:
        """Zero value of the per-device persistent state (None = stateless)."""
        return None

    # two-tier wire-cost model (bytes per device per step); benchmarks,
    # launch/dryrun.py and the scripts/check.sh smoke consume both tiers
    def bytes_per_device(self, ctx: StrategyContext) -> WireBytes:
        raise NotImplementedError


def _owner_base(ctx: StrategyContext) -> jax.Array:
    return jax.lax.axis_index(ctx.axes) * ctx.block_size


def _owner_accumulate(ctx: StrategyContext, req_ids, grads, acc_local,
                      base):
    """The reverse-shuffle scatter-add behind the `kernel_impl` seam:
    `ctx.kernel_impl="xla"` is `sparse.owner_accumulate`'s scatter-add,
    the pallas impls reduce sorted runs with the masked-matmul
    `segment_sum_sorted` combiner first (one owner add per unique
    feature). Dispatch lives in `repro.kernels.ops.owner_accumulate`."""
    return ops.owner_accumulate(req_ids, grads, acc_local, base,
                                impl=ctx.kernel_impl)


def _chunked_all_to_all(x: jax.Array, axes, num_chunks: int) -> jax.Array:
    """`jax.lax.all_to_all(x, axes, 0, 0, tiled=True)` split into micro
    collectives over the capacity axis (axis 1).

    Every (destination-row, capacity-slot) element is routed exactly as the
    monolithic exchange routes it, so the result is bit-identical; what
    changes is the lowering — `num_chunks` independent all-to-alls whose
    async start/done pairs XLA's latency-hiding scheduler can dispatch
    early and overlap with the compute between them, instead of one bulk
    transfer serializing the step.
    """
    cap = x.shape[1]
    n = max(1, min(num_chunks, cap))
    if n == 1:
        return jax.lax.all_to_all(x, axes, 0, 0, tiled=True)
    bounds = [cap * i // n for i in range(n + 1)]
    parts = [jax.lax.all_to_all(x[:, lo:hi], axes, 0, 0, tiled=True)
             for lo, hi in zip(bounds, bounds[1:], strict=False) if hi > lo]
    return jnp.concatenate(parts, axis=1)


def _sparse_distribute(ctx, cold_loc, cold_ids, a2a_fn=None):
    """The paper's Algorithm 4: request shuffle + owner lookup + response.

    `a2a_fn(x)` is the exchange primitive for the two (P, cap) buffers —
    the monolithic tiled all_to_all by default; overlap-aware strategies
    substitute a micro-chunked equivalent."""
    if a2a_fn is None:
        a2a_fn = lambda x: jax.lax.all_to_all(  # noqa: E731
            x, ctx.axes, 0, 0, tiled=True)
    routing = sparse.route_build(cold_ids, ctx.num_shards, ctx.block_size,
                                 ctx.capacity)
    with jax.named_scope("exchange"):
        req_recv = a2a_fn(routing.req_ids)
    resp = sparse.owner_apply(req_recv, cold_loc, _owner_base(ctx))
    with jax.named_scope("exchange"):
        resp_back = a2a_fn(resp)
    theta_cold = sparse.route_return(routing, resp_back)
    return theta_cold, {"routing": routing, "req_recv": req_recv,
                        "cold_ids": cold_ids, "overflow": routing.overflow}


def _dense_accumulate(ctx, cold_loc, grads_flat, cold_ids):
    """Local dense accumulation: the (F,) per-device gradient vector."""
    f = cold_loc.shape[0] * ctx.num_shards
    return jnp.zeros((f,), jnp.float32).at[
        jnp.where(cold_ids >= 0, cold_ids, f)
    ].add(jnp.where(cold_ids >= 0, grads_flat, 0.0), mode="drop")


def _dense_reduce(ctx, cold_loc, grads_flat, cold_ids):
    """Dense accumulate + psum_scatter: every device folds its gradients
    into a full-length vector; one collective delivers owner blocks."""
    gfull = _dense_accumulate(ctx, cold_loc, grads_flat, cold_ids)
    return jax.lax.psum_scatter(gfull, ctx.axes, scatter_dimension=0,
                                tiled=True)


class AllToAllStrategy(DistributionStrategy):
    """Paper-faithful DPMR shuffle in both directions."""

    name = "a2a"

    def distribute(self, ctx, cold_loc, cold_ids):
        return _sparse_distribute(ctx, cold_loc, cold_ids)

    def reduce(self, ctx, cold_loc, grads_flat, fwd):
        send = sparse.combine_grads(fwd["routing"], grads_flat)
        with jax.named_scope("exchange"):
            recv = jax.lax.all_to_all(send, ctx.axes, 0, 0, tiled=True)
        return _owner_accumulate(ctx, fwd["req_recv"], recv,
                                 jnp.zeros_like(cold_loc),
                                 _owner_base(ctx))

    def bytes_per_device(self, ctx):
        # 3 (P, cap) f32 buffers (requests, responses, grad sums); a
        # device RECEIVES the (Pi-1) same-pod buckets over ICI and the
        # (P-Pi) buckets addressed from other pods over DCN — its own
        # bucket never leaves the chip
        pi = ctx.inner_shards
        outer = 3 * (ctx.num_shards - pi) * ctx.capacity * 4
        return WireBytes(inner=3 * (pi - 1) * ctx.capacity * 4, outer=outer)


class AllGatherStrategy(DistributionStrategy):
    """Ship-the-table baseline (the paper's comparison point)."""

    name = "allgather"

    def distribute(self, ctx, cold_loc, cold_ids):
        table = jax.lax.all_gather(cold_loc, ctx.axes, tiled=True)
        theta_cold = jnp.where(cold_ids >= 0,
                               table[jnp.clip(cold_ids, 0)], 0.0)
        return theta_cold, {"cold_ids": cold_ids,
                            "overflow": jnp.zeros((), jnp.int32)}

    def reduce(self, ctx, cold_loc, grads_flat, fwd):
        return _dense_reduce(ctx, cold_loc, grads_flat, fwd["cold_ids"])

    def bytes_per_device(self, ctx):
        # forward ring all_gather + reduce psum_scatter: every device
        # receives the (P-1) remote blocks of |F|/P rows; the (P-Pi)
        # blocks owned by other pods cross DCN
        pi = ctx.inner_shards
        inner = 2 * ctx.block_size * (pi - 1) * 4
        outer = 2 * ctx.block_size * (ctx.num_shards - pi) * 4
        return WireBytes(inner=inner, outer=outer)


class PsumScatterStrategy(DistributionStrategy):
    """Hybrid: sparse shuffle forward, dense psum_scatter reduce.

    Keeps the forward wire cost |F|-independent while collapsing the reduce
    into one fused collective — attractive when the backward shuffle (not
    the lookup) is the bottleneck and a transient (|F|,) accumulation
    buffer per device is affordable.
    """

    name = "psum_scatter"

    def distribute(self, ctx, cold_loc, cold_ids):
        return _sparse_distribute(ctx, cold_loc, cold_ids)

    def reduce(self, ctx, cold_loc, grads_flat, fwd):
        return _dense_reduce(ctx, cold_loc, grads_flat, fwd["cold_ids"])

    def bytes_per_device(self, ctx):
        pi = ctx.inner_shards
        po_cross = ctx.num_shards - pi
        inner = (2 * (pi - 1) * ctx.capacity * 4
                 + ctx.block_size * (pi - 1) * 4)
        outer = (2 * po_cross * ctx.capacity * 4
                 + ctx.block_size * po_cross * 4)
        return WireBytes(inner=inner, outer=outer)


def _hier_remap(cold_ids: jax.Array, po: int, pi: int,
                block: int) -> jax.Array:
    """Bijection global id -> (inner_owner, mirror_row) contiguous space.

    Row r is owned by device d = r // block with pod q = d // Pi and inner
    index i = d % Pi. After the pod-axis all_gather, device (*, i) holds a
    mirror of all pods' i-blocks, laid out pod-major; relabelling
    r' = i * (Po*block) + q*block + (r % block) makes mirror ownership
    contiguous-block again (block size Po*block over Pi owners), so the
    unmodified routing kernels drive the inner-only exchange.
    """
    q = cold_ids // (pi * block)
    inner_owner = (cold_ids // block) % pi
    off = cold_ids % block
    remapped = inner_owner * (po * block) + q * block + off
    return jnp.where(cold_ids >= 0, remapped, -1)


class HierarchicalA2AStrategy(DistributionStrategy):
    """Two-level exchange over the (pod, ICI) tiers.

    Forward: all_gather over `outer_axes` mirrors, on every device, the
    table blocks of its inner-peer devices in every pod (Po blocks); the
    sparse request/response all-to-all then runs ONLY over `inner_axes`,
    against the mirror, with ids relabelled by `_hier_remap`. Reduce: the
    reverse inner shuffle accumulates per-feature sums into the mirror
    layout, then ONE psum_scatter over `outer_axes` crosses DCN carrying
    the already-reduced per-pod partials and lands each owner's block.

    With a single pod (Po == 1) this is bit-identical to `a2a`. The inner
    capacity is Po x the flat capacity (requests concentrate on Pi owners
    instead of P), so overflow behaviour matches `a2a` at equal headroom.
    """

    name = "hier_a2a"

    def _inner_capacity(self, ctx, n):
        return int(min(n, ctx.capacity * ctx.outer_shards))

    def distribute(self, ctx, cold_loc, cold_ids):
        po, pi = ctx.outer_shards, ctx.inner_shards
        if po == 1:
            return _sparse_distribute(ctx, cold_loc, cold_ids)
        block = ctx.block_size
        mirror = jax.lax.all_gather(cold_loc, ctx.outer_axes,
                                    tiled=True)            # (Po*block,)
        rem = _hier_remap(cold_ids, po, pi, block)
        if pi == 1:
            # one device per pod: the mirror is the whole table, look up
            # locally; DCN still only carries the dense block exchanges
            theta_cold = jnp.where(cold_ids >= 0,
                                   mirror[jnp.clip(rem, 0)], 0.0)
            return theta_cold, {"cold_ids": cold_ids, "rem_ids": rem,
                                "overflow": jnp.zeros((), jnp.int32)}
        cap_i = self._inner_capacity(ctx, cold_ids.shape[0])
        routing = sparse.route_build(rem, pi, po * block, cap_i)
        req_recv = jax.lax.all_to_all(routing.req_ids, ctx.inner_axes,
                                      0, 0, tiled=True)
        base = jax.lax.axis_index(ctx.inner_axes) * (po * block)
        resp = sparse.owner_apply(req_recv, mirror, base)
        resp_back = jax.lax.all_to_all(resp, ctx.inner_axes, 0, 0,
                                       tiled=True)
        theta_cold = sparse.route_return(routing, resp_back)
        return theta_cold, {"routing": routing, "req_recv": req_recv,
                            "cold_ids": cold_ids,
                            "overflow": routing.overflow}

    def _mirror_accumulate(self, ctx, cold_loc, grads_flat, fwd):
        """Inner-tier gradient reduce up to (not including) the DCN leg.

        Returns the (Po*block,) mirror accumulator whose segment q holds
        this pod's partial sums for pod q's owner block — everything the
        strategy does before the single outer-tier collective. This is the
        composition seam: `ComposedStrategy` swaps the psum_scatter that
        follows for a lossy outer leg while reusing this inner exchange.
        Requires Po > 1 (with one pod there is no mirror layout).
        """
        po, pi = ctx.outer_shards, ctx.inner_shards
        block = ctx.block_size
        if pi == 1:
            rem = fwd["rem_ids"]
            f_mirror = po * block
            return jnp.zeros((f_mirror,), jnp.float32).at[
                jnp.where(rem >= 0, rem, f_mirror)
            ].add(jnp.where(rem >= 0, grads_flat, 0.0), mode="drop")
        send = sparse.combine_grads(fwd["routing"], grads_flat)
        recv = jax.lax.all_to_all(send, ctx.inner_axes, 0, 0,
                                  tiled=True)
        base = jax.lax.axis_index(ctx.inner_axes) * (po * block)
        return _owner_accumulate(
            ctx, fwd["req_recv"], recv,
            jnp.zeros((po * block,), grads_flat.dtype), base)

    def reduce(self, ctx, cold_loc, grads_flat, fwd):
        po = ctx.outer_shards
        if po == 1:
            send = sparse.combine_grads(fwd["routing"], grads_flat)
            recv = jax.lax.all_to_all(send, ctx.axes, 0, 0, tiled=True)
            return _owner_accumulate(ctx, fwd["req_recv"], recv,
                                     jnp.zeros_like(cold_loc),
                                     _owner_base(ctx))
        mirror_acc = self._mirror_accumulate(ctx, cold_loc, grads_flat, fwd)
        # per-pod partials cross DCN exactly once: segment q of the mirror
        # accumulator is pod q's owner block, summed across pods
        return jax.lax.psum_scatter(mirror_acc, ctx.outer_axes,
                                    scatter_dimension=0, tiled=True)

    def bytes_per_device(self, ctx):
        po, pi = ctx.outer_shards, ctx.inner_shards
        # inner: the full sparse shuffle at Po-scaled capacity (all ICI),
        # received from the (Pi-1) inner peers
        inner = 3 * (pi - 1) * (ctx.capacity * po) * 4
        # outer: forward pod all_gather of the local block + reduce
        # psum_scatter of per-pod partials, both ring over Po
        outer = 2 * ctx.block_size * (po - 1) * 4
        return WireBytes(inner=inner, outer=outer)


class CompressedReduceStrategy(DistributionStrategy):
    """Sparse forward + int8 block-quantized dense reduce with error
    feedback (the optim/compression.py scheme on the strategy seam).

    Reduce path: the (F,) per-device gradient vector is compensated with
    the carried error state, block-quantized (`optim.compression.quantize`,
    one f32 scale per `compression.BLOCK` values), and exchanged as int8 by
    destination segment (all_to_all); receivers dequantize and sum their
    own block. The residual `(g + err) - dequant(q)` becomes the new carry,
    so quantization error is re-injected next step (EF-SGD / 1-bit Adam
    lineage) and SGD/Adagrad convergence tracks the exact strategies.

    The carry is per-device and |F|-sized — the engine persists it in
    `DPMRState.strat` and it rides through save()/restore() so a resumed
    run continues bit-identically. On the full-batch accumulation path
    the engine freezes the carry (`fwd["accumulate"]`), so the reduce
    falls back to the exact dense path there — quantizing against a
    frozen residual would re-inject it once per accumulated batch.
    """

    name = "compressed_reduce"

    def distribute(self, ctx, cold_loc, cold_ids):
        return _sparse_distribute(ctx, cold_loc, cold_ids)

    def init_carry(self, ctx):
        return jnp.zeros((ctx.num_shards * ctx.block_size,), jnp.float32)

    def _padded_block(self, ctx) -> int:
        qb = compression.BLOCK
        return -(-ctx.block_size // qb) * qb

    def reduce(self, ctx, cold_loc, grads_flat, fwd):
        if fwd.get("accumulate", False):
            # full-batch accumulation path (engine grad_step): the carry
            # is frozen there, so quantizing against it would re-inject a
            # restored residual once per accumulated batch instead of
            # once. Use the exact dense reduce and leave the residual
            # untouched (same discipline as topk_reduce).
            return (_dense_reduce(ctx, cold_loc, grads_flat,
                                  fwd["cold_ids"]), fwd["carry"])
        p = ctx.num_shards
        block = ctx.block_size
        qb = compression.BLOCK
        bp = self._padded_block(ctx)
        gfull = _dense_accumulate(ctx, cold_loc, grads_flat,
                                  fwd["cold_ids"])
        comp = gfull + fwd["carry"]                        # error feedback
        seg = jnp.pad(comp.reshape(p, block), ((0, 0), (0, bp - block)))
        q, scale = compression.quantize(seg.reshape(-1))   # (p*bp/qb, qb)
        new_carry = comp - compression.dequantize(
            q, scale, p * bp).reshape(p, bp)[:, :block].reshape(-1)
        # int8 on the wire: exchange by destination segment, dequantize and
        # sum the received contributions to this device's block
        q_recv = jax.lax.all_to_all(q.reshape(p, bp), ctx.axes, 0, 0,
                                    tiled=True)            # (p, bp) int8
        s_recv = jax.lax.all_to_all(scale.reshape(p, bp // qb), ctx.axes,
                                    0, 0, tiled=True)      # (p, bp/qb) f32
        deq = (q_recv.astype(jnp.float32).reshape(p, bp // qb, qb)
               * s_recv[..., None])
        grad = deq.reshape(p, bp)[:, :block].sum(axis=0)
        return grad, new_carry

    def bytes_per_device(self, ctx):
        pi = ctx.inner_shards
        po_cross = ctx.num_shards - pi
        bp = self._padded_block(ctx)
        per_peer = bp + (bp // compression.BLOCK) * 4      # int8 + scales
        inner = 2 * (pi - 1) * ctx.capacity * 4 + (pi - 1) * per_peer
        outer = 2 * po_cross * ctx.capacity * 4 + po_cross * per_peer
        return WireBytes(inner=inner, outer=outer)


class TopKReduceStrategy(DistributionStrategy):
    """Sparse forward + top-k sparsified reverse shuffle with per-device
    error feedback (gradient sparsification on the strategy seam).

    Forward is the paper's shuffle unchanged. On the reduce side each
    device combines its per-feature gradient sums into the (P, cap) send
    buffer, compensates every slot with the carried residual of that slot's
    FEATURE (`carry[feature_id]`), and then sends, per destination owner,
    only the k = ceil(topk_frac * cap) largest-magnitude slots — as (value
    f32, global id int32) pairs, so the wire carries k·P pairs instead of
    cap·P f32 slots. Owners scatter-add the received pairs exactly like
    `a2a` does. Slots that lost the top-k race bank their compensated
    gradient in the residual (`new_carry[feature] = compensated`); selected
    slots reset theirs to zero — EF-SGD lineage, so dropped coordinates are
    re-injected when the feature next appears and SGD/Adagrad convergence
    tracks the exact strategies (benchmarks/strategy_overlap.py sweeps
    loss-vs-k).

    The carry is per-device and |F|-sized, lives in `DPMRState.strat`,
    rides through `engine.save()`/`restore()` bit-exactly, and is reset to
    zeros by `runtime/elastic.py` resharding (a residual is per-device
    state, meaningless under a different shard count). `topk_frac=1.0`
    keeps every slot and the residual stays identically zero.

    Error feedback is only sound where the carry ADVANCES — the per-step
    train_step path. On the full-batch accumulation path the engine
    freezes the carry (`fwd["accumulate"]`, see `core.dpmr`), so this
    strategy detects it and runs the exact a2a reverse shuffle instead:
    fit() gets exact epoch gradients, fit_sgd() gets the sparsified wire.
    """

    name = "topk_reduce"

    def distribute(self, ctx, cold_loc, cold_ids):
        return _sparse_distribute(ctx, cold_loc, cold_ids)

    def init_carry(self, ctx):
        return jnp.zeros((ctx.num_shards * ctx.block_size,), jnp.float32)

    def _k(self, ctx) -> int:
        return compression.topk_count(ctx.capacity, ctx.topk_frac)

    def reduce(self, ctx, cold_loc, grads_flat, fwd):
        if fwd.get("accumulate", False):
            # full-batch accumulation path (engine grad_step): the carry
            # is frozen there — many grad_steps feed ONE update — so
            # sparsifying would permanently drop (1 - k/cap) of the epoch
            # gradient and re-inject any restored residual once per
            # accumulated batch instead of once. Fall back to the exact
            # reverse shuffle and leave the carry untouched; the top-k
            # wire savings apply to the per-step (SGD) path only.
            send = sparse.combine_grads(fwd["routing"], grads_flat)
            recv = jax.lax.all_to_all(send, ctx.axes, 0, 0, tiled=True)
            grad = _owner_accumulate(ctx, fwd["req_recv"], recv,
                                     jnp.zeros_like(cold_loc),
                                     _owner_base(ctx))
            return grad, fwd["carry"]
        f = ctx.num_shards * ctx.block_size
        k = self._k(ctx)
        send = sparse.combine_grads(fwd["routing"], grads_flat)  # (P, cap)
        ids = fwd["routing"].req_ids                             # (P, cap)
        valid = ids >= 0
        # fused compensate + rank-by-|magnitude| + pack: every live slot is
        # compensated with the residual its feature banked the last time it
        # lost the top-k race, each destination row keeps its k
        # largest-|comp| live slots, and losers bank their compensated
        # value as the new residual — one kernels.ops.select_pack call
        # (`kernel_impl="xla"` runs the original five-op chain, see
        # kernels/ref.py:select_pack_ref; the Pallas kernel is bit-exact)
        carry_slots = fwd["carry"][jnp.clip(ids, 0, f - 1)]
        vals_k, ids_k, resid = ops.select_pack(send, ids, carry_slots,
                                               k=k, impl=ctx.kernel_impl)
        # residual scatter: selected features flushed to zero, losers bank
        # their compensated slot (feature ids are unique per device, so a
        # plain scatter-set is race-free; absent features keep theirs, and
        # invalid slots are dropped)
        new_carry = fwd["carry"].at[
            jnp.where(valid, ids, f).reshape(-1)
        ].set(resid.reshape(-1), mode="drop")
        v_recv = jax.lax.all_to_all(vals_k, ctx.axes, 0, 0, tiled=True)
        i_recv = jax.lax.all_to_all(ids_k, ctx.axes, 0, 0, tiled=True)
        grad = _owner_accumulate(ctx, i_recv, v_recv,
                                 jnp.zeros_like(cold_loc),
                                 _owner_base(ctx))
        return grad, new_carry

    def bytes_per_device(self, ctx):
        # forward: the 2 (P, cap) f32 request/response buffers of a2a;
        # reduce: k of cap slots per peer, each an (f32 value, int32 id)
        # pair — the k/cap reduction lands on BOTH tiers
        pi = ctx.inner_shards
        po_cross = ctx.num_shards - pi
        k = self._k(ctx)
        inner = 2 * (pi - 1) * ctx.capacity * 4 + (pi - 1) * k * 8
        outer = 2 * po_cross * ctx.capacity * 4 + po_cross * k * 8
        return WireBytes(inner=inner, outer=outer)


class OverlapA2AStrategy(AllToAllStrategy):
    """Overlap-aware `a2a`: the same exchanges, lowered as micro-chunks.

    Every (P, cap) all-to-all of the paper's shuffle is split into
    `num_chunks` independent collectives over capacity-slot ranges
    (`_chunked_all_to_all`). Element routing is untouched, so parameters
    and gradients are BIT-IDENTICAL to `a2a` on any mesh — only the
    schedule differs: XLA lowers each micro-chunk to its own async
    start/done pair, letting the latency-hiding scheduler dispatch the
    next chunk (and the reverse shuffle of already-landed gradient
    chunks) while the inference matmul of the step still runs, instead of
    serializing one bulk transfer against the compute. Wire bytes equal
    `a2a` (inherited model); what the strategy buys is overlap, measured
    by benchmarks/strategy_overlap.py.
    """

    name = "overlap_a2a"
    num_chunks = 4      # micro-chunks per exchange; capacity-bounded

    def _a2a(self, ctx, x):
        return _chunked_all_to_all(x, ctx.axes, self.num_chunks)

    def distribute(self, ctx, cold_loc, cold_ids):
        return _sparse_distribute(ctx, cold_loc, cold_ids,
                                  a2a_fn=lambda x: self._a2a(ctx, x))

    def reduce(self, ctx, cold_loc, grads_flat, fwd):
        send = sparse.combine_grads(fwd["routing"], grads_flat)
        recv = self._a2a(ctx, send)
        return _owner_accumulate(ctx, fwd["req_recv"], recv,
                                 jnp.zeros_like(cold_loc),
                                 _owner_base(ctx))


class OuterLeg:
    """The DCN half of a per-tier composition.

    A leg replaces the single outer-tier collective of a hierarchical
    strategy's reduce — it receives the (Po*block,) mirror accumulator
    (segment q = this pod's partials for pod q's owner block) and must
    deliver this device's (block,) owner gradient by exchanging ONLY over
    `ctx.outer_axes`. Legs may keep an error-feedback residual: declare
    its static length via `carry_len` (0 = stateless) and advance it in
    `reduce_outer`; `ComposedStrategy` namespaces it into the composed
    carry that the engine persists in `DPMRState.strat`.
    """

    name: str = "leg"

    def carry_len(self, ctx: StrategyContext) -> int:
        """Static residual length on this geometry (0 = no carry)."""
        return 0

    def reduce_outer(self, ctx: StrategyContext, mirror_acc: jax.Array,
                     carry: jax.Array) -> tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def reduce_bytes(self, ctx: StrategyContext) -> int:
        """DCN bytes a device receives on the reduce leg (Po > 1)."""
        raise NotImplementedError


class TopKOuterLeg(OuterLeg):
    """Top-k sparsified DCN reduce: each pod sends, per destination pod,
    only the k = ceil(topk_frac * block) largest-|g| rows of its partial
    block as (value f32, row int32) pairs; losers bank an error-feedback
    residual over the (Po*block,) mirror layout, re-injected when the row
    next carries gradient mass (same EF-SGD lineage as `topk_reduce`, but
    applied AFTER the exact inner exchange, so only the cheap-to-compress
    cross-pod partials are sparsified).
    """

    name = "topk"

    def _k(self, ctx) -> int:
        return compression.topk_count(ctx.block_size, ctx.topk_frac)

    def carry_len(self, ctx):
        return ctx.outer_shards * ctx.block_size

    def reduce_outer(self, ctx, mirror_acc, carry):
        po, block = ctx.outer_shards, ctx.block_size
        k = self._k(ctx)
        comp = (mirror_acc + carry).reshape(po, block)   # error feedback
        top_idx, top_mask = compression.topk_select(jnp.abs(comp), k)
        vals_k = jnp.take_along_axis(comp, top_idx, axis=1)   # (Po, k)
        ids_k = top_idx.astype(jnp.int32)                # within-block rows
        new_carry = jnp.where(top_mask, 0.0, comp).reshape(-1)
        v_recv = jax.lax.all_to_all(vals_k, ctx.outer_axes, 0, 0,
                                    tiled=True)
        i_recv = jax.lax.all_to_all(ids_k, ctx.outer_axes, 0, 0,
                                    tiled=True)
        grad = jnp.zeros((block,), jnp.float32).at[
            i_recv.reshape(-1)
        ].add(v_recv.reshape(-1))
        return grad, new_carry

    def reduce_bytes(self, ctx):
        # k (f32 value, int32 row) pairs from each of the (Po-1) other pods
        return (ctx.outer_shards - 1) * self._k(ctx) * 8


class Int8OuterLeg(OuterLeg):
    """Int8 block-quantized DCN reduce: the per-pod partial blocks cross
    the slow tier as int8 + per-`compression.BLOCK` f32 scales (the
    `compressed_reduce` scheme, applied to the outer tier only), with the
    quantization residual banked as an error-feedback carry over the
    (Po*block,) mirror layout.
    """

    name = "int8"

    def _padded_block(self, ctx) -> int:
        qb = compression.BLOCK
        return -(-ctx.block_size // qb) * qb

    def carry_len(self, ctx):
        return ctx.outer_shards * ctx.block_size

    def reduce_outer(self, ctx, mirror_acc, carry):
        po, block = ctx.outer_shards, ctx.block_size
        qb = compression.BLOCK
        bp = self._padded_block(ctx)
        comp = mirror_acc + carry                        # error feedback
        seg = jnp.pad(comp.reshape(po, block), ((0, 0), (0, bp - block)))
        q, scale = compression.quantize(seg.reshape(-1))
        new_carry = comp - compression.dequantize(
            q, scale, po * bp).reshape(po, bp)[:, :block].reshape(-1)
        q_recv = jax.lax.all_to_all(q.reshape(po, bp), ctx.outer_axes,
                                    0, 0, tiled=True)    # (Po, bp) int8
        s_recv = jax.lax.all_to_all(scale.reshape(po, bp // qb),
                                    ctx.outer_axes, 0, 0, tiled=True)
        deq = (q_recv.astype(jnp.float32).reshape(po, bp // qb, qb)
               * s_recv[..., None])
        grad = deq.reshape(po, bp)[:, :block].sum(axis=0)
        return grad, new_carry

    def reduce_bytes(self, ctx):
        bp = self._padded_block(ctx)
        per_peer = bp + (bp // compression.BLOCK) * 4    # int8 + scales
        return (ctx.outer_shards - 1) * per_peer


class ComposedStrategy(DistributionStrategy):
    """Per-tier composition: a hierarchical member's exact exchange on the
    fast inner tier (ICI), an `OuterLeg`'s lossy reduce on the slow outer
    tier (DCN).

    The cut point is the member's `_mirror_accumulate` seam: forward and
    the inner gradient shuffle are the member's own (exact), and only the
    single DCN crossing of the reduce is replaced by the leg. With one pod
    (Po == 1) the composition degenerates to the member exactly — it is
    then stateless and bit-identical. Carries are namespaced per member by
    `carry_layout`; on the full-batch accumulation path the composition
    falls back to the member's exact reduce with the carry frozen (the
    same discipline every lossy built-in follows).
    """

    def __init__(self, inner: DistributionStrategy, leg: OuterLeg):
        self.inner = inner
        self.leg = leg
        self.name = f"{inner.name}+{leg.name}"

    def carry_layout(self, ctx) -> list[tuple[str, int]]:
        """Namespaced `(member_name, length)` segments of the composed
        carry, in `DPMRState.strat` order. Only stateful members appear;
        today that is at most the outer leg (`register_composition`
        requires a stateless inner member)."""
        n = self.leg.carry_len(ctx) if ctx.outer_shards > 1 else 0
        return [(self.leg.name, n)] if n else []

    def distribute(self, ctx, cold_loc, cold_ids):
        return self.inner.distribute(ctx, cold_loc, cold_ids)

    def init_carry(self, ctx):
        total = sum(n for _, n in self.carry_layout(ctx))
        if total == 0:
            return None
        return jnp.zeros((total,), jnp.float32)

    def reduce(self, ctx, cold_loc, grads_flat, fwd):
        if ctx.outer_shards == 1:
            # single tier: the member IS the composition (stateless here)
            return self.inner.reduce(ctx, cold_loc, grads_flat, fwd)
        if fwd.get("accumulate", False):
            # full-batch accumulation: the carry is frozen, so sparsifying
            # or quantizing the DCN leg would drop epoch-gradient mass /
            # re-inject a restored residual once per accumulated batch.
            # Run the member's exact reduce and pass the carry through.
            return (self.inner.reduce(ctx, cold_loc, grads_flat, fwd),
                    fwd["carry"])
        mirror_acc = self.inner._mirror_accumulate(ctx, cold_loc,
                                                   grads_flat, fwd)
        return self.leg.reduce_outer(ctx, mirror_acc, fwd["carry"])

    def bytes_per_device(self, ctx):
        member = self.inner.bytes_per_device(ctx)
        po = ctx.outer_shards
        if po == 1:
            return member
        # inner tier is the member's own (exact) exchange; outer = the
        # forward pod all_gather of the local block + the leg's reduce
        outer = ctx.block_size * (po - 1) * 4 + self.leg.reduce_bytes(ctx)
        return WireBytes(inner=member.inner, outer=outer)


_REGISTRY: dict[str, DistributionStrategy] = {}


def register_strategy(name: str, strategy: DistributionStrategy = None):
    """Register a strategy instance, or use as a class decorator:

        @register_strategy("mine")
        class Mine(DistributionStrategy): ...
    """
    if strategy is not None:
        # shallow-copy so aliasing an existing instance doesn't rename it
        inst = copy.copy(strategy)
        inst.name = name
        _REGISTRY[name] = inst
        return inst

    def _decorate(cls):
        inst = cls() if isinstance(cls, type) else cls
        inst.name = name
        _REGISTRY[name] = inst
        return cls

    return _decorate


def get_strategy(name: str) -> DistributionStrategy:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown distribution strategy {name!r}; "
            f"registered: {sorted(_REGISTRY)}") from None


def list_strategies() -> list[str]:
    return sorted(_REGISTRY)


def register_composition(inner_name: str, leg: OuterLeg,
                         name: str | None = None) -> ComposedStrategy:
    """Register `ComposedStrategy(get_strategy(inner_name), leg)` under
    `"<inner>+<leg>"` (or `name`). The inner member must expose the
    `_mirror_accumulate` seam (hierarchical reduce split at the DCN
    crossing) and must be stateless — its own carry would have to be
    namespaced alongside the leg's, which no member needs today.
    """
    inner = get_strategy(inner_name)
    if not hasattr(inner, "_mirror_accumulate"):
        raise TypeError(
            f"strategy {inner_name!r} has no _mirror_accumulate seam; "
            "only hierarchical strategies whose reduce isolates the DCN "
            "crossing can take a composed outer leg")
    composed = ComposedStrategy(inner, leg)
    register_strategy(name or composed.name, composed)
    return composed


register_strategy("a2a", AllToAllStrategy())
register_strategy("allgather", AllGatherStrategy())
register_strategy("psum_scatter", PsumScatterStrategy())
register_strategy("hier_a2a", HierarchicalA2AStrategy())
register_strategy("compressed_reduce", CompressedReduceStrategy())
register_strategy("topk_reduce", TopKReduceStrategy())
register_strategy("overlap_a2a", OverlapA2AStrategy())
register_composition("hier_a2a", TopKOuterLeg())
register_composition("hier_a2a", Int8OuterLeg())
