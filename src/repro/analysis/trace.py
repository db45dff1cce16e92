"""Jaxpr tracing of distribution strategies on analytic meshes.

Every strategy method runs inside shard_map, so its collectives name mesh
axes (`jax.lax.all_to_all(x, ctx.axes, ...)`). To trace those bodies
WITHOUT devices we wrap them in `jax.shard_map` over a
`jax.sharding.AbstractMesh` of the analytic axis sizes (an abstract mesh
names axes and sizes but holds no devices) and run `jax.make_jaxpr` on
abstract inputs — the body jaxpr then records each collective primitive
with its axis names, operand shapes, and dtypes, for any geometry (a
512-chip two-pod mesh traces fine on a CPU-only host).

`trace_strategy` produces the auditor's raw material: the collective list
of `distribute`, of the carry-advancing `reduce` path (SGD), and — for
stateful strategies — of the frozen-carry accumulate path, plus the
structural facts the contract rules consume (does `reduce` return a
`(grad, carry)` pair, is the carry passed through untouched on the
accumulate path).
"""
from __future__ import annotations

from collections.abc import Iterable, Sequence
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.sharding import AbstractMesh, PartitionSpec as P

# collectives the wire model understands (see wire.py); anything else that
# smells like a collective is still EXTRACTED so the auditor can reject it
# as unmodeled instead of silently under-counting
KNOWN_COLLECTIVES = frozenset({
    "all_to_all", "all_gather", "reduce_scatter", "psum", "pmax", "pmin",
    "ppermute",
})


class Collective(NamedTuple):
    """One collective equation extracted from a jaxpr."""

    prim: str                      # primitive name ("all_to_all", ...)
    axes: tuple[str, ...]          # mesh axes the collective runs over
    shapes: tuple[tuple[int, ...], ...]   # per-operand (per-device) shapes
    dtypes: tuple[str, ...]        # per-operand dtypes
    out_shapes: tuple[tuple[int, ...], ...]
    out_dtypes: tuple[str, ...]

    @property
    def signature(self) -> tuple:
        """Hashable identity used for signature pinning / set comparison."""
        return (self.prim, self.axes, self.shapes, self.dtypes)

    @property
    def in_bytes(self) -> int:
        """Total bytes of the per-device operand buffers."""
        return sum(_nbytes(s, d) for s, d in zip(self.shapes, self.dtypes,
                                                 strict=True))

    @property
    def out_bytes(self) -> int:
        return sum(_nbytes(s, d) for s, d in zip(self.out_shapes,
                                                 self.out_dtypes,
                                                 strict=True))

    def describe(self) -> str:
        ops = ", ".join(f"{d}{list(s)}" for s, d in
                        zip(self.shapes, self.dtypes, strict=True))
        return f"{self.prim}[{','.join(self.axes) or '·'}]({ops})"


def _nbytes(shape: tuple[int, ...], dtype: str) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * jnp.dtype(dtype).itemsize


def _axis_tuple(axis_name) -> tuple[str, ...]:
    if axis_name is None:
        return ()
    if isinstance(axis_name, (tuple, list)):
        return tuple(str(a) for a in axis_name)
    return (str(axis_name),)


def _per_device(fn, axis_sizes: dict):
    """`fn` as the body of a shard_map over an abstract mesh of
    `axis_sizes`. Every input and output is replicated (`P()`), so the
    body sees exactly the avals it is given, as one device would."""
    mesh = AbstractMesh(tuple(int(v) for v in axis_sizes.values()),
                        tuple(axis_sizes))
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def trace_jaxpr(fn, axis_sizes: dict, *avals) -> ClosedJaxpr:
    """The per-device jaxpr of `fn(*avals)` on an analytic mesh.

    `axis_sizes` maps mesh axis name -> size; the abstract mesh makes
    `axis_index` / `all_to_all` / ... traceable without any devices.
    `avals` are `jax.ShapeDtypeStruct` pytrees. Returns the shard_map
    BODY, so its invars/outvars are `fn`'s own arguments and results.
    """
    outer = jax.make_jaxpr(_per_device(fn, axis_sizes))(*avals)
    (eqn,) = outer.jaxpr.eqns
    return ClosedJaxpr(eqn.params["jaxpr"], ())


def _eval_shape(fn, axis_sizes: dict, *avals):
    return jax.eval_shape(_per_device(fn, axis_sizes), *avals)


def _subjaxprs(eqn) -> Iterable:
    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x


def collect_collectives(jaxpr) -> list[Collective]:
    """Recursively extract collective eqns (incl. pjit/scan/shard_map
    sub-jaxprs) from a Jaxpr or ClosedJaxpr."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out: list[Collective] = []

    def walk(jpr):
        for eqn in jpr.eqns:
            name = eqn.primitive.name
            if name in KNOWN_COLLECTIVES or name.startswith("p") and \
                    "axis_name" in eqn.params:
                axes = _axis_tuple(eqn.params.get("axis_name",
                                                  eqn.params.get("axes")))
                if name == "psum" and "axes" in eqn.params:
                    axes = _axis_tuple(eqn.params["axes"])
                if eqn.params.get("axis_index_groups") is not None:
                    # built-ins never use groups; record under a distinct
                    # prim name so the wire model rejects it explicitly
                    name = name + "[grouped]"
                out.append(Collective(
                    prim=name, axes=axes,
                    shapes=tuple(tuple(v.aval.shape) for v in eqn.invars),
                    dtypes=tuple(str(v.aval.dtype) for v in eqn.invars),
                    out_shapes=tuple(tuple(v.aval.shape)
                                     for v in eqn.outvars),
                    out_dtypes=tuple(str(v.aval.dtype)
                                     for v in eqn.outvars)))
            for sub in _subjaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    return out


class StrategyTrace(NamedTuple):
    """Everything the contract rules need to know about one strategy on one
    analytic geometry."""

    distribute: tuple[Collective, ...]    # forward (theta shuffle) path
    reduce: tuple[Collective, ...]        # carry-advancing reduce (SGD path)
    accumulate: tuple[Collective, ...] | None  # frozen-carry path (stateful)
    stateful: bool                        # init_carry returned an array
    carry_1d_f32: bool | None             # carry is 1-D float32
    reduce_pair: bool | None              # reduce returned (grad, carry)
    carry_aval_preserved: bool | None     # returned carry aval == input
    carry_passthrough: bool | None        # accumulate path returns the
    #                                       carry INVAR itself (jaxpr-level
    #                                       proof it is untouched)
    wire_dtypes_accumulate: tuple[str, ...] | None  # dtypes on the wire
    #                                       on the accumulate path
    fwd_overflow: bool = False            # distribute's fwd dict carries a
    #                                       scalar int32 "overflow"


def batch_elems(ctx) -> int:
    """Analytic per-device flat feature-slot count used for tracing.

    Large enough that hier_a2a's inner capacity min(n, cap*Po) never
    clamps — the wire models are stated for the unclamped regime."""
    return max(256, 2 * ctx.capacity * max(ctx.outer_shards, 1))


def trace_strategy(strategy, ctx, axis_sizes: dict,
                   n: int | None = None) -> StrategyTrace:
    """Trace `strategy` on the analytic geometry (`ctx`, `axis_sizes`).

    `ctx` must carry REAL axis names (ctx.axes) matching `axis_sizes`;
    `n` is the flat per-device feature-slot count (ids/grads length),
    defaulting to `batch_elems(ctx)`.
    """
    n = batch_elems(ctx) if n is None else n
    cold = jax.ShapeDtypeStruct((ctx.block_size,), jnp.float32)
    ids = jax.ShapeDtypeStruct((n,), jnp.int32)
    grads = jax.ShapeDtypeStruct((n,), jnp.float32)

    # -- forward ------------------------------------------------------------
    def dist(cold_loc, cold_ids):
        return strategy.distribute(ctx, cold_loc, cold_ids)

    theta_fwd = _eval_shape(dist, axis_sizes, cold, ids)
    _, fwd_avals = theta_fwd
    ov = fwd_avals.get("overflow") if isinstance(fwd_avals, dict) else None
    fwd_overflow = (ov is not None and tuple(ov.shape) == ()
                    and ov.dtype == jnp.int32)
    dist_ops = tuple(collect_collectives(
        trace_jaxpr(dist, axis_sizes, cold, ids)))

    carry_aval = None
    stateful = False
    carry_1d_f32 = None
    carry0 = jax.eval_shape(lambda: strategy.init_carry(ctx))
    if carry0 is not None:
        stateful = True
        carry_aval = jax.ShapeDtypeStruct(tuple(carry0.shape),
                                          carry0.dtype)
        carry_1d_f32 = (carry0.ndim == 1
                        and carry0.dtype == jnp.float32)

    # -- reduce (both carry modes for stateful strategies) ------------------
    def make_reduce(accumulating: bool):
        if not stateful:
            def red(cold_loc, g, fwd):
                return strategy.reduce(ctx, cold_loc, g, fwd)
            return red

        def red(carry, cold_loc, g, fwd):
            # carry FIRST so its jaxpr invar index is fixed at 0 — the
            # passthrough proof below compares outvars against invars[0]
            return strategy.reduce(
                ctx, cold_loc, g,
                {**fwd, "carry": carry, "accumulate": accumulating})
        return red

    reduce_pair = None
    carry_preserved = None
    if stateful:
        out_avals = _eval_shape(make_reduce(False), axis_sizes,
                                carry_aval, cold, grads, fwd_avals)
        reduce_pair = (isinstance(out_avals, tuple) and len(out_avals) == 2)
        if reduce_pair:
            carry_preserved = (
                tuple(out_avals[1].shape) == tuple(carry_aval.shape)
                and out_avals[1].dtype == carry_aval.dtype)
        red_jpr = trace_jaxpr(make_reduce(False), axis_sizes,
                              carry_aval, cold, grads, fwd_avals)
        reduce_ops = tuple(collect_collectives(red_jpr))

        acc_jpr = trace_jaxpr(make_reduce(True), axis_sizes,
                              carry_aval, cold, grads, fwd_avals)
        acc_ops = tuple(collect_collectives(acc_jpr))
        # the accumulate path must leave the carry untouched; at jaxpr
        # level that means the second output IS the carry input variable
        outvars = acc_jpr.jaxpr.outvars
        invars = acc_jpr.jaxpr.invars
        passthrough = len(outvars) >= 2 and outvars[-1] is invars[0]
        wire_dtypes = tuple(sorted({d for c in acc_ops for d in c.dtypes}))
        return StrategyTrace(
            distribute=dist_ops, reduce=reduce_ops, accumulate=acc_ops,
            stateful=True, carry_1d_f32=carry_1d_f32,
            reduce_pair=reduce_pair, carry_aval_preserved=carry_preserved,
            carry_passthrough=passthrough,
            wire_dtypes_accumulate=wire_dtypes, fwd_overflow=fwd_overflow)

    out_aval = _eval_shape(make_reduce(False), axis_sizes,
                           cold, grads, fwd_avals)
    reduce_pair = isinstance(out_aval, tuple)
    red_jpr = trace_jaxpr(make_reduce(False), axis_sizes,
                          cold, grads, fwd_avals)
    reduce_ops = tuple(collect_collectives(red_jpr))
    return StrategyTrace(
        distribute=dist_ops, reduce=reduce_ops, accumulate=None,
        stateful=False, carry_1d_f32=None, reduce_pair=reduce_pair,
        carry_aval_preserved=None, carry_passthrough=None,
        wire_dtypes_accumulate=None, fwd_overflow=fwd_overflow)


def signature_multiset(ops: Sequence[Collective]) -> tuple:
    """Order-independent, hashable multiset of collective signatures."""
    return tuple(sorted(c.signature for c in ops))
