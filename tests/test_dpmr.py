"""DPMR engine tests: routing oracles, hot sharding, convergence, strategy
equivalence (a2a == allgather == psum_scatter == hier_a2a == dense oracle,
compressed_reduce within quantization error), the two-tier wire model, the
DPMREngine facade, capacity/overflow accounting, and checkpoint roundtrip
(including the persistent strategy carry)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DistributionStrategy, DPMREngine, WireBytes,
                       get_strategy, hot_ids_from_corpus, list_strategies,
                       register_strategy)
from repro.api.strategies import StrategyContext
from repro.configs.base import DPMRConfig
from repro.core import dpmr, hot_sharding, reference, sparse
from repro.data import get_source, sparse_corpus
from repro.launch.mesh import make_host_mesh, tier_axes, tier_shards

F = 1 << 12
SPEC = sparse_corpus.CorpusSpec(num_features=F, features_per_sample=16,
                                signal_features=256, seed=0)
# strategies that are EXACT (bit-identical parameters when nothing
# overflows); compressed_reduce / topk_reduce are lossy and tested for
# parity instead
STRATEGIES = ("a2a", "allgather", "psum_scatter", "hier_a2a",
              "overlap_a2a")


def _batches(batch_size, num_batches, start=0):
    """Batches [start, num_batches) — the legacy `sparse_corpus.batches`
    call convention, served by the zipf_sparse data source."""
    src = get_source("zipf_sparse", spec=SPEC, batch_size=batch_size)
    return src.iter_batches(start=start, limit=num_batches - start)


def _cfg(**kw):
    base = dict(num_features=F, max_features_per_sample=16, iterations=2,
                learning_rate=1.0, max_hot=32)
    base.update(kw)
    return DPMRConfig(**base)


# ---------------------------------------------------------------------------
# pure routing / hot-sharding oracles
# ---------------------------------------------------------------------------


def test_routing_roundtrip_oracle():
    rng = np.random.default_rng(0)
    p, f = 4, 64
    block, cap = f // p, 24
    ids = rng.integers(-1, f, size=(57,)).astype(np.int32)
    r = sparse.route_build(jnp.asarray(ids), p, block, cap)
    assert int(r.overflow) == 0
    table = rng.normal(size=(f,)).astype(np.float32)
    resp = np.zeros((p, cap), np.float32)
    req = np.asarray(r.req_ids)
    for o in range(p):
        resp[o] = np.where(req[o] >= 0, table[np.clip(req[o], 0, f - 1)], 0)
    vals = sparse.route_return(r, jnp.asarray(resp))
    expect = np.where(ids >= 0, table[np.clip(ids, 0, f - 1)], 0)
    np.testing.assert_allclose(np.asarray(vals), expect, rtol=1e-6)


def test_grad_combine_oracle():
    rng = np.random.default_rng(1)
    p, f = 4, 64
    block, cap = f // p, 24
    ids = rng.integers(-1, f, size=(57,)).astype(np.int32)
    grads = rng.normal(size=ids.shape).astype(np.float32)
    r = sparse.route_build(jnp.asarray(ids), p, block, cap)
    send = np.asarray(sparse.combine_grads(r, jnp.asarray(grads)))
    got = np.zeros(f)
    req = np.asarray(r.req_ids)
    for o in range(p):
        for c in range(cap):
            if req[o, c] >= 0:
                got[req[o, c]] += send[o, c]
    want = np.zeros(f)
    np.add.at(want, np.clip(ids, 0, f - 1), np.where(ids >= 0, grads, 0))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_overflow_counted_when_capacity_too_small():
    ids = jnp.arange(32, dtype=jnp.int32)     # 32 unique, all owner 0
    r = sparse.route_build(ids, 2, 64, 8)     # cap 8 < 32 uniques
    assert int(r.overflow) == 24


def test_hot_split():
    occurrences = np.repeat(np.arange(8), [100, 1, 50, 1, 1, 80, 1, 1])
    hot_np = hot_sharding.select_hot(occurrences, threshold=0.1, max_hot=4)
    hot = jnp.asarray(hot_np)
    assert set(hot_np[hot_np < 2**31 - 1]) == {0, 2, 5}
    ids = jnp.asarray([0, 1, 5, -1, 3], jnp.int32)
    slot, is_hot, cold = hot_sharding.split_hot(ids, hot)
    assert list(np.asarray(is_hot)) == [True, False, True, False, False]
    assert list(np.asarray(cold)) == [-1, 1, -1, -1, 3]


# ---------------------------------------------------------------------------
# capacity model
# ---------------------------------------------------------------------------


def test_capacity_model():
    """capacity(): >= 16, multiple of 8, ~factor x uniform mean, <= n."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg()
    n = 128 * cfg.max_features_per_sample
    cap = dpmr.capacity(cfg, 128, mesh)
    assert cap == dpmr.capacity_for_shards(cfg, 128, dpmr.num_shards(mesh))
    assert cap % 8 == 0 or cap == n
    assert 16 <= cap <= n
    # tiny factor clamps to the floor of 16; huge factor clamps to n
    assert dpmr.capacity(cfg, 128, mesh, factor=1e-9) == 16
    assert dpmr.capacity(cfg, 128, mesh, factor=1e9) == n
    # analytic shard counts: capacity shrinks ~1/p
    c32 = dpmr.capacity_for_shards(cfg, 2048, 32)
    c256 = dpmr.capacity_for_shards(cfg, 2048, 256)
    assert c256 < c32


@pytest.mark.parametrize("distribution", ["a2a", "psum_scatter",
                                          "hier_a2a", "compressed_reduce",
                                          "topk_reduce", "overlap_a2a"])
def test_overflow_metric_nonzero_at_tiny_capacity(distribution):
    """Sparse-forward strategies report dropped uniques through the
    `overflow` metric when cap_factor is forced tiny, and zero at the
    default factor."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution=distribution)
    batch = sparse_corpus.make_batch(SPEC, 128, 0)

    tiny = DPMREngine(cfg, mesh, cap_factor=1e-9)
    assert tiny.step_fns(128).capacity == 16
    m = tiny.train_step(batch)
    assert m["overflow"] > 0, m

    dflt = DPMREngine(cfg, mesh)
    m = dflt.train_step(batch)
    assert m["overflow"] == 0, m


def test_overflow_metric_zero_for_allgather():
    """The ship-the-table strategy has no capacity to overflow."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution="allgather")
    batch = sparse_corpus.make_batch(SPEC, 128, 0)
    m = DPMREngine(cfg, mesh, cap_factor=1e-9).train_step(batch)
    assert m["overflow"] == 0, m


# ---------------------------------------------------------------------------
# strategy registry
# ---------------------------------------------------------------------------


def test_strategy_registry():
    assert set(STRATEGIES) <= set(list_strategies())
    assert get_strategy("a2a").name == "a2a"
    with pytest.raises(KeyError):
        get_strategy("nope")

    @register_strategy("test_alias_a2a")
    class AliasA2A(type(get_strategy("a2a"))):
        pass

    assert "test_alias_a2a" in list_strategies()
    assert isinstance(get_strategy("test_alias_a2a"), DistributionStrategy)


def test_registered_strategy_trains():
    """A user-registered strategy is selectable via cfg.distribution."""
    register_strategy("test_custom", get_strategy("a2a"))
    mesh = make_host_mesh(1, 1)
    eng = DPMREngine(_cfg(distribution="test_custom"), mesh)
    hist = eng.fit_sgd(_batches(128, 2))
    assert len(hist) == 2 and np.isfinite(hist[-1]["loss"])


# ---------------------------------------------------------------------------
# engine vs dense oracle / strategy equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("distribution", STRATEGIES)
def test_dpmr_matches_dense_oracle(distribution):
    """The full staged pipeline == numpy logistic regression GD."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution=distribution, max_hot=16)
    batches = list(_batches(128, 3))
    hot = hot_ids_from_corpus(cfg, batches, mesh)
    eng = DPMREngine(cfg, mesh, hot_ids=hot)
    eng.fit(lambda: iter(batches))
    f = dpmr.padded_features(cfg, mesh)
    oracle = reference.gd_iterations(cfg, batches, cfg.iterations, f)
    # full theta: cold + hot written back at hot_ids
    theta = reference.engine_table(eng.state)
    np.testing.assert_allclose(theta, np.asarray(oracle), atol=2e-4)


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad"])
def test_fit_sgd_matches_reference(optimizer):
    """Minibatch SGD through the engine == `core.reference.sgd_steps`, the
    routing-free float32 reference the chip smoke compares against."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(optimizer=optimizer, max_hot=16)
    batches = list(_batches(128, 6))
    hot = hot_ids_from_corpus(cfg, batches[:2], mesh)
    eng = DPMREngine(cfg, mesh, hot_ids=hot)
    hist = eng.fit_sgd(batches)
    assert all(h["overflow"] == 0 for h in hist)
    theta, _, losses = reference.sgd_steps(
        cfg, batches, dpmr.padded_features(cfg, mesh))
    np.testing.assert_allclose([h["loss"] for h in hist], losses, rtol=1e-5)
    np.testing.assert_allclose(reference.engine_table(eng.state),
                               np.asarray(theta), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kw", [{"optimizer": "momentum"},
                                {"schedule": "warmup_cosine"}])
def test_reference_refuses_what_it_lacks(kw):
    """The reference writes out sgd and adagrad at a constant rate; any
    other optimizer or schedule is an error, never a silent mismatch."""
    with pytest.raises(ValueError, match="the reference has"):
        reference.sgd_steps(_cfg(**kw), [])
    with pytest.raises(ValueError, match="the reference has"):
        reference.gd_iterations(_cfg(**kw), [], 1)


def test_strategies_agree():
    """All registered built-in strategies produce identical parameters and
    losses on a 1-device mesh (they only differ in wire bytes)."""
    mesh = make_host_mesh(1, 1)
    batches = list(_batches(128, 3))
    colds, hists = {}, {}
    for dist in STRATEGIES:
        eng = DPMREngine(_cfg(distribution=dist), mesh)
        hists[dist] = [h["loss"] for h in eng.fit(lambda: iter(batches))]
        colds[dist] = np.asarray(eng.state.cold)
    for dist in STRATEGIES[1:]:
        np.testing.assert_allclose(colds[STRATEGIES[0]], colds[dist],
                                   atol=1e-5)
        np.testing.assert_allclose(hists[STRATEGIES[0]], hists[dist],
                                   rtol=1e-6)


def test_sgd_training_reduces_loss_and_learns():
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(optimizer="adagrad", learning_rate=2.0)
    eng = DPMREngine(cfg, mesh)
    history = eng.fit_sgd(_batches(256, 40))
    ev = eng.evaluate(list(_batches(256, 52, start=50)))
    first = np.mean([h["loss"] for h in history[:5]])
    last = np.mean([h["loss"] for h in history[-5:]])
    assert last < first - 0.01, (first, last)
    assert ev["f_avg"] > 0.5, ev


def test_classify_probabilities_valid():
    mesh = make_host_mesh(1, 1)
    eng = DPMREngine(_cfg(), mesh)
    eng.fit_sgd(_batches(128, 5))
    b = sparse_corpus.make_batch(SPEC, 128, seed=777)
    probs = eng.predict({"ids": b["ids"], "vals": b["vals"]})
    assert probs.shape == (128,)
    assert np.all((probs >= 0) & (probs <= 1))


# ---------------------------------------------------------------------------
# two-tier wire model + hierarchical / compressed strategies
# ---------------------------------------------------------------------------


def test_bytes_per_device_two_tier_contract():
    """Every registered built-in returns WireBytes; on a single-tier
    geometry nothing crosses DCN and the totals match the received-bytes
    models ((P-1) peers — a device's own chunk never travels);
    inner + outer == total always."""
    p, cap, block = 256, 64, 1 << 14
    flat = StrategyContext(axes=(), num_shards=p, block_size=block,
                           capacity=cap)
    received = {"a2a": 3 * (p - 1) * cap * 4,
                "allgather": 2 * block * (p - 1) * 4,
                "psum_scatter": 2 * (p - 1) * cap * 4
                + block * (p - 1) * 4}
    for name in list_strategies():
        wb = get_strategy(name).bytes_per_device(flat)
        assert isinstance(wb, WireBytes), name
        assert wb.outer == 0, (name, wb)
        assert wb.total == wb.inner + wb.outer
        if name in received:
            assert wb.total == received[name], (name, wb)


def test_hier_a2a_crosses_dcn_with_fewer_bytes():
    """The headline property: on a multi-pod geometry at the paper's
    full-batch regime, hier_a2a's DCN bytes (table block mirror + per-pod
    partials) are strictly below flat a2a's (cross-pod request volume)."""
    p, po = 512, 2
    cfg = DPMRConfig(num_features=1 << 30, max_features_per_sample=64)
    cap = dpmr.capacity_for_shards(cfg, (1 << 24) // p, p)
    ctx = StrategyContext(axes=(), num_shards=p,
                          block_size=(1 << 30) // p, capacity=cap,
                          outer_shards=po)
    a2a = get_strategy("a2a").bytes_per_device(ctx)
    hier = get_strategy("hier_a2a").bytes_per_device(ctx)
    assert hier.outer < a2a.outer, (hier, a2a)
    # the trade: hier pays with MORE inner (ICI) volume, never less
    assert hier.inner >= a2a.inner


def test_strategy_context_exposes_mesh_tiers():
    """make_step_fns threads the (outer, inner) axis split of the mesh to
    the strategies via StepFns.ctx; a pod-less mesh has an empty outer
    tier."""
    mesh = make_host_mesh(1, 1)
    assert tier_axes(mesh) == ((), ("data", "model"))
    assert tier_shards(mesh) == (1, 1)
    fns = DPMREngine(_cfg(), mesh).step_fns(128)
    assert fns.ctx.axes == ("data", "model")
    assert fns.ctx.outer_axes == () and fns.ctx.outer_shards == 1
    assert fns.ctx.inner_axes == ("data", "model")
    assert fns.ctx.inner_shards == fns.ctx.num_shards == 1


def test_compressed_reduce_convergence_parity():
    """compressed_reduce (int8 reduce + error feedback) trains to within
    1% of a2a's final loss on the same SGD run."""
    mesh = make_host_mesh(1, 1)
    final = {}
    for dist in ("a2a", "compressed_reduce"):
        eng = DPMREngine(_cfg(distribution=dist, optimizer="adagrad",
                              learning_rate=2.0), mesh)
        hist = eng.fit_sgd(_batches(256, 40))
        final[dist] = np.mean([h["loss"] for h in hist[-5:]])
    rel = abs(final["compressed_reduce"] - final["a2a"]) / final["a2a"]
    assert rel < 0.01, final


def test_compressed_reduce_error_feedback_state():
    """The quantization residual lives in DPMRState.strat: zero at init,
    nonzero after a step, untouched by stateless strategies."""
    mesh = make_host_mesh(1, 1)
    batch = sparse_corpus.make_batch(SPEC, 128, 0)

    eng = DPMREngine(_cfg(distribution="compressed_reduce"), mesh)
    f = dpmr.padded_features(eng.cfg, mesh)
    assert eng.state.strat.shape == (f,)          # per-device (F,) carry
    assert float(jnp.abs(eng.state.strat).sum()) == 0.0
    eng.train_step(batch)
    assert float(jnp.abs(eng.state.strat).sum()) > 0.0

    plain = DPMREngine(_cfg(), mesh)              # stateless: placeholder
    assert plain.state.strat.shape == (1,)
    plain.train_step(batch)
    assert float(jnp.abs(plain.state.strat).sum()) == 0.0


def test_compressed_reduce_carry_checkpoint_roundtrip(tmp_path):
    """save()/restore() persists the error-feedback carry: a restored run
    continues bit-identically to the uninterrupted one (it would diverge
    if the carry were dropped)."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution="compressed_reduce", optimizer="adagrad",
               learning_rate=2.0)
    batches = list(_batches(128, 6))

    full = DPMREngine(cfg, mesh)
    full.fit_sgd(iter(batches))

    part = DPMREngine(cfg, mesh)
    part.fit_sgd(iter(batches[:3]))
    assert float(jnp.abs(part.state.strat).sum()) > 0.0
    part.save(str(tmp_path))

    resumed = DPMREngine(cfg, mesh)
    resumed.restore(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(part.state.strat),
                                  np.asarray(resumed.state.strat))
    resumed.fit_sgd(iter(batches[3:]))
    for a, b in zip(full.state, resumed.state, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_warns_on_strategy_mismatch(tmp_path):
    """A checkpoint trained under one strategy restored into an engine
    configured for another must not silently adopt the foreign carry."""
    mesh = make_host_mesh(1, 1)
    eng = DPMREngine(_cfg(distribution="a2a"), mesh)
    eng.fit_sgd(_batches(128, 2))
    eng.save(str(tmp_path))
    other = DPMREngine(_cfg(distribution="psum_scatter"), mesh)
    with pytest.warns(RuntimeWarning, match="distribution"):
        other.restore(str(tmp_path))


def test_restore_unregistered_strategy_raises_value_error(tmp_path):
    """A checkpoint whose saved strategy is NOT in this process's registry
    (e.g. a session-local composition that was never re-registered) must
    fail with a ValueError naming the missing strategy — not leak the
    registry's KeyError."""
    from repro.api.strategies import _REGISTRY

    mesh = make_host_mesh(1, 1)
    register_strategy("ephemeral_xyz", get_strategy("a2a"))
    try:
        eng = DPMREngine(_cfg(distribution="ephemeral_xyz"), mesh)
        eng.fit_sgd(_batches(128, 2))
        eng.save(str(tmp_path))
    finally:
        _REGISTRY.pop("ephemeral_xyz", None)

    other = DPMREngine(_cfg(distribution="a2a"), mesh)
    with pytest.raises(ValueError, match="ephemeral_xyz"):
        other.restore(str(tmp_path))


# ---------------------------------------------------------------------------
# topk_reduce / overlap_a2a: sparsified & overlap-aware exchanges
# ---------------------------------------------------------------------------


def test_overlap_a2a_bit_identical_to_a2a():
    """The micro-chunked exchange must change the SCHEDULE only: losses
    and parameters equal a2a's bit for bit (no float-order tolerance)."""
    mesh = make_host_mesh(1, 1)
    batches = list(_batches(128, 4))
    out = {}
    for dist in ("a2a", "overlap_a2a"):
        eng = DPMREngine(_cfg(distribution=dist), mesh)
        hist = eng.fit_sgd(iter(batches))
        out[dist] = (np.asarray(eng.state.cold),
                     [h["loss"] for h in hist])
    np.testing.assert_array_equal(out["a2a"][0], out["overlap_a2a"][0])
    assert out["a2a"][1] == out["overlap_a2a"][1]


def test_topk_frac_one_degenerates_to_a2a():
    """topk_frac=1.0 keeps every slot: parameters match a2a and the
    residual stays identically zero."""
    mesh = make_host_mesh(1, 1)
    batches = list(_batches(128, 3))
    ref = DPMREngine(_cfg(distribution="a2a"), mesh)
    ref.fit_sgd(iter(batches))
    full = DPMREngine(_cfg(distribution="topk_reduce", topk_frac=1.0), mesh)
    full.fit_sgd(iter(batches))
    np.testing.assert_allclose(np.asarray(ref.state.cold),
                               np.asarray(full.state.cold), atol=1e-6)
    assert float(jnp.abs(full.state.strat).sum()) == 0.0


def test_topk_error_feedback_state():
    """At a sparsifying fraction the dropped slots bank a residual in
    DPMRState.strat; it is per-device |F|-sized like compressed_reduce's."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution="topk_reduce", topk_frac=0.1)
    eng = DPMREngine(cfg, mesh)
    f = dpmr.padded_features(cfg, mesh)
    assert eng.state.strat.shape == (f,)
    assert float(jnp.abs(eng.state.strat).sum()) == 0.0
    eng.train_step(sparse_corpus.make_batch(SPEC, 128, 0))
    assert float(jnp.abs(eng.state.strat).sum()) > 0.0


def test_topk_reduce_convergence_parity():
    """Error feedback keeps topk_reduce within 1% of a2a's final loss on
    the SGD run (the tighter 0.1%-at-default gate lives in
    benchmarks/strategy_overlap.py)."""
    mesh = make_host_mesh(1, 1)
    final = {}
    for dist in ("a2a", "topk_reduce"):
        eng = DPMREngine(_cfg(distribution=dist, optimizer="adagrad",
                              learning_rate=2.0, topk_frac=0.1), mesh)
        hist = eng.fit_sgd(_batches(256, 40))
        final[dist] = np.mean([h["loss"] for h in hist[-5:]])
    rel = abs(final["topk_reduce"] - final["a2a"]) / final["a2a"]
    assert rel < 0.01, final


def test_stateful_strategies_exact_on_full_batch_fit():
    """The fit() accumulation path freezes the carry (fwd["accumulate"]);
    both lossy built-ins must fall back to their exact reduce there —
    parameters match a2a (topk even at an aggressive fraction), and the
    residual never accumulates (sparsifying/quantizing against a frozen
    carry would drop gradient mass / re-inject a restored residual once
    per accumulated batch)."""
    mesh = make_host_mesh(1, 1)
    batches = list(_batches(128, 3))
    ref = DPMREngine(_cfg(distribution="a2a"), mesh)
    ref.fit(lambda: iter(batches))
    for dist in ("topk_reduce", "compressed_reduce"):
        eng = DPMREngine(_cfg(distribution=dist, topk_frac=0.05), mesh)
        eng.fit(lambda: iter(batches))
        np.testing.assert_allclose(np.asarray(ref.state.cold),
                                   np.asarray(eng.state.cold), atol=1e-5)
        assert float(jnp.abs(eng.state.strat).sum()) == 0.0, dist


def test_restored_carry_frozen_through_fit():
    """A nonzero residual restored from an SGD run must ride through a
    fit() epoch untouched (re-injected zero times, not once per batch)."""
    mesh = make_host_mesh(1, 1)
    batches = list(_batches(128, 4))
    for dist in ("topk_reduce", "compressed_reduce"):
        eng = DPMREngine(_cfg(distribution=dist, topk_frac=0.05), mesh)
        eng.fit_sgd(iter(batches))            # builds a live residual
        before = np.asarray(eng.state.strat).copy()
        assert np.abs(before).sum() > 0.0, dist
        eng.fit(lambda: iter(batches), iterations=1)
        np.testing.assert_array_equal(before, np.asarray(eng.state.strat))


def test_topk_carry_checkpoint_roundtrip(tmp_path):
    """save()/restore() persists the sparsification residual bit-exactly:
    a restored run continues identically to the uninterrupted one."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution="topk_reduce", topk_frac=0.1,
               optimizer="adagrad", learning_rate=2.0)
    batches = list(_batches(128, 6))

    full = DPMREngine(cfg, mesh)
    full.fit_sgd(iter(batches))

    part = DPMREngine(cfg, mesh)
    part.fit_sgd(iter(batches[:3]))
    assert float(jnp.abs(part.state.strat).sum()) > 0.0
    part.save(str(tmp_path))

    resumed = DPMREngine(cfg, mesh)
    resumed.restore(str(tmp_path))
    np.testing.assert_array_equal(np.asarray(part.state.strat),
                                  np.asarray(resumed.state.strat))
    resumed.fit_sgd(iter(batches[3:]))
    for a, b in zip(full.state, resumed.state, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_topk_carry_reset_on_elastic_reshard():
    """Elastic resharding must zero the residual (per-device state is
    meaningless under a new shard count) while keeping the parameters."""
    from repro.runtime.elastic import reshard_dpmr_state

    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution="topk_reduce", topk_frac=0.1)
    eng = DPMREngine(cfg, mesh)
    eng.fit_sgd(_batches(128, 3))
    assert float(jnp.abs(eng.state.strat).sum()) > 0.0
    new = reshard_dpmr_state(eng.state, cfg, mesh)
    assert float(jnp.abs(new.strat).sum()) == 0.0
    assert new.strat.shape == eng.state.strat.shape
    np.testing.assert_array_equal(np.asarray(new.cold),
                                  np.asarray(eng.state.cold))


def test_restore_warns_on_topk_frac_mismatch(tmp_path):
    """A topk_reduce residual accumulated at one sparsification level
    restored under another must be called out."""
    mesh = make_host_mesh(1, 1)
    eng = DPMREngine(_cfg(distribution="topk_reduce", topk_frac=0.1), mesh)
    eng.fit_sgd(_batches(128, 2))
    eng.save(str(tmp_path))
    other = DPMREngine(_cfg(distribution="topk_reduce", topk_frac=0.5),
                       mesh)
    with pytest.warns(RuntimeWarning, match="topk_frac"):
        other.restore(str(tmp_path))


def test_topk_selection_helpers_oracle():
    """compression.topk_count / topk_mask against numpy ground truth."""
    from repro.optim import compression

    assert compression.topk_count(16, 0.25) == 4
    assert compression.topk_count(16, 1e-9) == 1      # floor at 1
    assert compression.topk_count(16, 1.0) == 16      # ceil at n
    assert compression.topk_count(10, 0.25) == 3      # ceil, not round
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    for k in (1, 7, 32):
        idx, mask = compression.topk_select(x, k)
        idx, mask = np.asarray(idx), np.asarray(mask)
        assert idx.shape == (5, k)
        assert mask.shape == x.shape and mask.sum(axis=1).tolist() == \
            [k] * 5
        np.testing.assert_array_equal(
            mask, np.asarray(compression.topk_mask(x, k)))
        for row, irow, mrow in zip(np.asarray(x), idx, mask, strict=True):
            top = set(sorted(row, reverse=True)[:k])
            assert set(row[mrow]) == top == set(row[irow])


def test_topk_and_overlap_wire_models():
    """topk_reduce cuts the reduce leg cap -> 2k pairs on BOTH tiers;
    overlap_a2a's bytes equal a2a's exactly (it buys schedule, not
    volume); ctx.topk_frac is threaded from DPMRConfig through StepFns."""
    from repro.optim import compression

    p, po, cap, block = 512, 2, 2048, 1 << 21
    for frac in (0.05, 0.25):
        ctx = StrategyContext(axes=(), num_shards=p, block_size=block,
                              capacity=cap, outer_shards=po,
                              topk_frac=frac)
        a2a = get_strategy("a2a").bytes_per_device(ctx)
        topk = get_strategy("topk_reduce").bytes_per_device(ctx)
        assert get_strategy("overlap_a2a").bytes_per_device(ctx) == a2a
        k = compression.topk_count(cap, frac)
        # forward legs match a2a's 2 buffers; reduce leg is k (val, id)
        # pairs per peer on each tier
        pi = ctx.inner_shards
        assert topk.inner == 2 * (pi - 1) * cap * 4 + (pi - 1) * k * 8
        assert topk.outer == 2 * (p - pi) * cap * 4 + (p - pi) * k * 8
        assert topk.total < a2a.total

    mesh = make_host_mesh(1, 1)
    fns = DPMREngine(_cfg(distribution="topk_reduce", topk_frac=0.125),
                     mesh).step_fns(128)
    assert fns.ctx.topk_frac == 0.125


# ---------------------------------------------------------------------------
# optimizer / schedule registries on the sparse face
# ---------------------------------------------------------------------------


def test_sparse_optimizer_registry():
    from repro.optim import optimizers

    assert {"sgd", "adagrad", "momentum"} <= set(
        optimizers.SPARSE_OPTIMIZERS)
    with pytest.raises(KeyError):
        optimizers.get_sparse_optimizer("nope")
    # momentum trains and differs from plain sgd
    mesh = make_host_mesh(1, 1)
    batches = list(_batches(256, 10))
    colds = {}
    for opt in ("sgd", "momentum"):
        eng = DPMREngine(_cfg(optimizer=opt, learning_rate=0.5), mesh)
        eng.fit_sgd(iter(batches))
        colds[opt] = np.asarray(eng.state.cold)
    assert np.max(np.abs(colds["sgd"] - colds["momentum"])) > 1e-7


def test_schedule_registry_on_sparse_face():
    from repro.optim import schedules

    with pytest.raises(KeyError):
        schedules.get_schedule_by_name("nope", 1.0)
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(schedule="warmup_cosine", warmup_steps=2, total_steps=8,
               learning_rate=1.0)
    eng = DPMREngine(cfg, mesh)
    assert eng.learning_rate() == 0.0          # step 0 of warmup
    hist = eng.fit_sgd(_batches(256, 8))
    assert np.isfinite(hist[-1]["loss"])
    assert eng.learning_rate() < cfg.learning_rate   # cosine decayed


# ---------------------------------------------------------------------------
# checkpointing through the engine
# ---------------------------------------------------------------------------


def test_engine_save_restore_roundtrip(tmp_path):
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(optimizer="adagrad", learning_rate=2.0)
    eng = DPMREngine(cfg, mesh)
    eng.fit_sgd(_batches(128, 6))
    step = eng.save(str(tmp_path))
    assert step == 6

    eng2 = DPMREngine(cfg, mesh)
    manifest = eng2.restore(str(tmp_path))
    assert manifest["extra"]["kind"] == "dpmr_sparse"
    for a, b in zip(eng.state, eng2.state, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training continues identically from the restored state
    batch = sparse_corpus.make_batch(SPEC, 128, seed=99)
    m1 = eng.train_step(batch)
    m2 = eng2.train_step(batch)
    assert m1 == m2
    np.testing.assert_array_equal(np.asarray(eng.state.cold),
                                  np.asarray(eng2.state.cold))


# ---------------------------------------------------------------------------
# deprecated fn-dict surface is GONE (one-release deprecation completed)
# ---------------------------------------------------------------------------


def test_legacy_fn_dict_surface_removed():
    """core.sparse_lr and StepFns dict access finished their one-release
    deprecation in the PR that added the data plane."""
    with pytest.raises(ImportError):
        from repro.core import sparse_lr  # noqa: F401
    from repro.core import api as core_api

    for gone in ("dpmr_train", "dpmr_train_sgd", "dpmr_classify", "evaluate"):
        assert not hasattr(core_api, gone), gone
    assert callable(core_api.hot_ids_from_corpus)   # re-homed, still public

    mesh = make_host_mesh(1, 1)
    fns = DPMREngine(_cfg(), mesh).step_fns(128)
    with pytest.raises(TypeError):
        fns["train_step"]           # dict-style access removed
    assert callable(fns.train_step)


# ---------------------------------------------------------------------------
# engine regression guards (empty corpus, step-fns cache bound)
# ---------------------------------------------------------------------------


def test_fit_empty_corpus_raises_value_error():
    """fit() with a batch_iter_fn that yields nothing must raise a clear
    ValueError, not ZeroDivisionError (regression)."""
    mesh = make_host_mesh(1, 1)
    eng = DPMREngine(_cfg(), mesh)
    with pytest.raises(ValueError, match="no batches"):
        eng.fit(lambda: iter([]))


def test_step_fns_cache_is_lru_bounded():
    """Every distinct batch size compiles once, but only `max_cached_fns`
    entries are retained (bucketed serving traffic must not leak)."""
    mesh = make_host_mesh(1, 1)
    eng = DPMREngine(_cfg(), mesh, max_cached_fns=2)
    for bs in (64, 128, 192):
        eng.step_fns(bs)
    assert list(eng._fns) == [128, 192]      # 64 evicted (least recent)
    eng.step_fns(128)                        # refresh 128
    eng.step_fns(64)                         # evicts 192
    assert list(eng._fns) == [128, 64]
    assert eng.fns is eng._fns[64]           # .fns == most recently used
    with pytest.raises(ValueError):
        DPMREngine(_cfg(), mesh, max_cached_fns=0)


# ---------------------------------------------------------------------------
# kernels / elastic integration
# ---------------------------------------------------------------------------


def test_engine_with_pallas_kernels_matches_jnp():
    """The full DPMR pipeline with the (interpreted) Pallas sigmoid-grad
    kernel is bit-identical to the jnp oracle path — the kernel is a true
    drop-in for the computeGradients map body."""
    mesh = make_host_mesh(1, 1)
    batches = list(_batches(128, 3))
    outs = {}
    for impl in ("jnp", "pallas_interpret"):
        eng = DPMREngine(_cfg(), mesh, kernel_impl=impl)
        eng.fit(lambda: iter(batches))
        outs[impl] = np.asarray(eng.state.cold)
    np.testing.assert_array_equal(outs["jnp"], outs["pallas_interpret"])


def test_segment_kernel_as_combiner():
    """The MXU segment-sum kernel can replace the scatter-add combiner:
    scattering its run-end totals delivers identical owner sums."""
    from repro.kernels import ops

    rng = np.random.default_rng(5)
    p, f = 4, 64
    block, cap = f // p, 64
    ids = rng.integers(-1, f, size=(57,)).astype(np.int32)
    grads = rng.normal(size=ids.shape).astype(np.float32)
    r = sparse.route_build(jnp.asarray(ids), p, block, cap)
    # scatter-add combiner (engine default)
    send_a = np.asarray(sparse.combine_grads(r, jnp.asarray(grads)))
    # kernel combiner: segment totals on the sorted stream, scatter run ends
    g_sorted = jnp.asarray(grads)[r.order]
    g_sorted = jnp.where(r.keep_s, g_sorted, 0.0)
    ids_sorted = jnp.where(r.keep_s, jnp.asarray(ids)[r.order], -1)
    totals = ops.segment_sum_sorted(ids_sorted, g_sorted,
                                    impl="pallas_interpret", block=16)
    send_b = jnp.zeros((p, cap), jnp.float32).at[
        jnp.where(r.keep_s, r.owner_s, p), r.pos_s
    ].add(totals, mode="drop")
    np.testing.assert_allclose(send_a, np.asarray(send_b), atol=1e-5)


def test_elastic_reshard_roundtrip():
    from repro.runtime.elastic import reshard_dpmr_state

    mesh = make_host_mesh(1, 1)
    eng = DPMREngine(_cfg(), mesh)
    eng.fit_sgd(_batches(128, 3))
    state2 = reshard_dpmr_state(eng.state, eng.cfg, mesh)
    np.testing.assert_array_equal(np.asarray(eng.state.cold),
                                  np.asarray(state2.cold))