"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")   # don't abort collection without it

from hypothesis import given, settings, strategies as st

from repro.api import autotune
from repro.api.strategies import (StrategyContext, get_strategy,
                                  list_strategies)
from repro.core import hot_sharding, sparse
from repro.kernels import ops
from repro.optim import compression

SET = dict(max_examples=25, deadline=None)

# the built-in registry at import time (other test modules register
# throwaway strategies at run time; the tuner properties are stated over
# the shipped set)
BUILTINS = tuple(list_strategies())


@st.composite
def id_arrays(draw, max_n=96, max_f=96):
    n = draw(st.integers(4, max_n))
    f = draw(st.integers(8, max_f))
    ids = draw(st.lists(st.integers(-1, f - 1), min_size=n, max_size=n))
    return np.asarray(ids, np.int32), f


@given(id_arrays(), st.integers(1, 4))
@settings(**SET)
def test_route_roundtrip_identity(ids_f, logp):
    """distributeParameters then restoreDocuments is the identity lookup
    for ANY id multiset, for any shard count, when capacity suffices."""
    ids, f = ids_f
    p = 2 ** logp
    f = -(-f // p) * p
    block = f // p
    cap = int(ids.size)                       # capacity always sufficient
    r = sparse.route_build(jnp.asarray(ids), p, block, cap)
    assert int(r.overflow) == 0
    table = np.arange(1, f + 1, dtype=np.float32)  # distinct values
    req = np.asarray(r.req_ids)
    resp = np.zeros((p, cap), np.float32)
    for o in range(p):
        resp[o] = np.where(req[o] >= 0, table[np.clip(req[o], 0, f - 1)], 0)
    vals = np.asarray(sparse.route_return(r, jnp.asarray(resp)))
    expect = np.where(ids >= 0, table[np.clip(ids, 0, f - 1)], 0)
    np.testing.assert_allclose(vals, expect)


@given(id_arrays(), st.integers(1, 3))
@settings(**SET)
def test_grad_conservation(ids_f, logp):
    """The reduce shuffle conserves total gradient mass per feature."""
    ids, f = ids_f
    p = 2 ** logp
    f = -(-f // p) * p
    block = f // p
    rng = np.random.default_rng(0)
    grads = rng.normal(size=ids.shape).astype(np.float32)
    r = sparse.route_build(jnp.asarray(ids), p, block, int(ids.size))
    send = np.asarray(sparse.combine_grads(r, jnp.asarray(grads)))
    # total mass (valid slots only) is conserved through the combiner
    np.testing.assert_allclose(send.sum(), grads[ids >= 0].sum(), atol=1e-4)


@given(st.integers(2, 6), st.integers(10, 200))
@settings(**SET)
def test_segment_sum_mass_conservation(nruns, n):
    rng = np.random.default_rng(nruns * n)
    ids = np.sort(rng.integers(0, nruns, size=n)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    out = ops.segment_sum_sorted(jnp.asarray(ids), jnp.asarray(g),
                                 impl="pallas_interpret", block=32)
    np.testing.assert_allclose(float(jnp.sum(out)), g.sum(), atol=1e-4)
    # one emission per distinct id
    assert int(jnp.sum(out != 0)) <= nruns


@given(st.integers(0, 2**31 - 2), st.integers(1, 64))
@settings(**SET)
def test_hot_split_partition(seed, max_hot):
    """hot + cold is a partition: every valid id goes to exactly one side."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(-1, 1000, size=64).astype(np.int32)
    hot = jnp.asarray(hot_sharding.select_hot(ids, 0.01, max_hot))
    slot, is_hot, cold = hot_sharding.split_hot(jnp.asarray(ids), hot)
    is_hot = np.asarray(is_hot)
    cold = np.asarray(cold)
    valid = ids >= 0
    assert np.all((cold[valid] >= 0) != is_hot[valid])
    assert np.all(cold[~valid] == -1)
    # hot slots decode back to the original id
    hot_np = np.asarray(hot)
    sl = np.asarray(slot)
    assert np.all(hot_np[sl[is_hot]] == ids[is_hot])


@given(st.integers(0, 10_000), st.integers(1, 8))
@settings(**SET)
def test_compression_error_feedback_bounded(seed, blocks):
    """Quantization error never exceeds half a quant step per element, and
    error feedback keeps the CUMULATIVE error bounded over steps."""
    rng = np.random.default_rng(seed)
    n = blocks * 64
    g = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    err = jnp.zeros_like(g)
    total_applied = jnp.zeros_like(g)
    total_true = jnp.zeros_like(g)
    for _ in range(4):
        q, scale = compression._quantize(
            jnp.pad(g + err, (0, (-n) % compression.BLOCK)))
        deq = compression._dequantize(q, scale, n)
        new_err = g + err - deq
        total_applied = total_applied + deq
        total_true = total_true + g
        err = new_err
    # with error feedback, cumulative applied = cumulative true - last error
    np.testing.assert_allclose(np.asarray(total_applied + err),
                               np.asarray(total_true), rtol=1e-5, atol=1e-5)


@given(st.integers(0, 1000))
@settings(**SET)
def test_cross_entropy_matches_numpy(seed):
    from repro.models.common import cross_entropy

    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(2, 5, 11)).astype(np.float32)
    labels = rng.integers(0, 11, size=(2, 5)).astype(np.int32)
    got = float(cross_entropy(jnp.asarray(logits), jnp.asarray(labels)))
    # numpy oracle
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    nll = -np.log(np.take_along_axis(p, labels[..., None], -1))[..., 0]
    np.testing.assert_allclose(got, nll.mean(), rtol=1e-5)


@given(st.sampled_from(["train_4k", "prefill_32k", "decode_32k"]),
       st.sampled_from(["granite-8b", "mixtral-8x22b", "xlstm-125m"]))
@settings(max_examples=9, deadline=None)
def test_batch_defs_consistent(shape_name, arch):
    """Input specs: batch dims always equal the shape's global batch."""
    from repro.configs import SHAPES
    from repro.models import registry
    from repro.sharding import Annotated

    spec = registry.get_spec(arch)
    shape = SHAPES[shape_name]
    defs = registry.batch_defs(spec, shape)
    toks = defs["tokens"] if "tokens" in defs else defs["cache"]
    leaves = jax.tree.leaves(
        defs, is_leaf=lambda x: isinstance(x, Annotated))
    assert all(isinstance(l, Annotated) for l in leaves)
    if shape.kind != "decode":
        assert defs["tokens"].shape == (shape.global_batch, shape.seq_len)
    else:
        assert defs["tokens"].shape == (shape.global_batch, 1)


# ---------------------------------------------------------------------------
# analytic geometry autotuner (repro.api.autotune)
# ---------------------------------------------------------------------------


@st.composite
def geometries(draw):
    """Analytic StrategyContexts: power-of-two shard counts with the pod
    factor dividing them, paper-plausible block/capacity ranges."""
    po = draw(st.sampled_from([1, 2, 4]))
    pi = 2 ** draw(st.integers(1, 6))
    block = 2 ** draw(st.integers(7, 14))
    cap = 2 ** draw(st.integers(4, 12))
    frac = draw(st.sampled_from([0.05, 0.25, 1.0]))
    return StrategyContext(axes=(), num_shards=po * pi, block_size=block,
                           capacity=cap, outer_shards=po, topk_frac=frac)


bandwidths = st.floats(1.0, 2000.0)


@given(geometries(), bandwidths, bandwidths)
@settings(**SET)
def test_autotuner_choice_is_optimal(ctx, inner_gbps, outer_gbps):
    """The chosen strategy never costs more than ANY candidate under the
    same per-tier bandwidths (independently recomputed costs)."""
    bw = autotune.WireBandwidth(inner_gbps, outer_gbps)
    ranked = autotune.score_strategies(ctx, bw, strategies=BUILTINS)
    chosen = autotune.choose_strategy(ctx, bw, strategies=BUILTINS)
    assert chosen == ranked[0].name
    for name in BUILTINS:
        cost = autotune.wire_cost(
            get_strategy(name).bytes_per_device(ctx), bw)
        assert ranked[0].cost_s <= cost


@given(geometries(), bandwidths, bandwidths, bandwidths)
@settings(**SET)
def test_autotuner_dcn_monotonicity(ctx, inner_gbps, bw_a, bw_b):
    """Raising the DCN cost (slower outer tier) never flips the tuner
    toward a strategy with MORE outer bytes — the exchange argument
    (c1-c2)(1/bw1-1/bw2) <= 0, stated over the real registry."""
    fast, slow = max(bw_a, bw_b), min(bw_a, bw_b)

    def pick(outer_gbps):
        return autotune.score_strategies(
            ctx, autotune.WireBandwidth(inner_gbps, outer_gbps),
            strategies=BUILTINS)[0]

    assert pick(slow).wire.outer <= pick(fast).wire.outer


@given(geometries(), bandwidths, bandwidths)
@settings(**SET)
def test_autotuner_ranking_deterministic(ctx, inner_gbps, outer_gbps):
    """Same inputs -> same ranking, and ties break by name (the ranking
    is exactly sorted by (cost, name))."""
    bw = autotune.WireBandwidth(inner_gbps, outer_gbps)
    r1 = autotune.score_strategies(ctx, bw, strategies=BUILTINS)
    r2 = autotune.score_strategies(ctx, bw, strategies=BUILTINS)
    assert [s.name for s in r1] == [s.name for s in r2]
    keys = [(s.cost_s, s.name) for s in r1]
    assert keys == sorted(keys)


@given(geometries(), bandwidths, bandwidths)
@settings(**SET)
def test_autotuner_require_exact_filters_lossy(ctx, inner_gbps, outer_gbps):
    """require_exact drops exactly the strategies that would carry
    error-feedback state on THIS geometry, and never all of them (the
    exact built-ins admit every geometry)."""
    bw = autotune.WireBandwidth(inner_gbps, outer_gbps)
    exact = autotune.score_strategies(ctx, bw, require_exact=True,
                                      strategies=BUILTINS)
    assert exact and all(not s.lossy for s in exact)
    for s in exact:
        assert get_strategy(s.name).init_carry(ctx) is None
