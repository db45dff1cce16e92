"""Universal strategy-conformance suite.

ONE parametrized harness, auto-discovered over `list_strategies()` —
including registered compositions (`hier_a2a+topk`, `hier_a2a+int8`) —
crossed with the audit geometries {1dev, pod8, multipod}. Registering a
new strategy or composition makes it appear here automatically; it cannot
merge without proving the full contract:

  analytic (no devices — jaxpr tracing on each geometry):
    * every rule in `repro.analysis.contracts` passes (W-MODEL, W-MATCH,
      W-OUTER, W-SINGLE, F-OVERFLOW, C-CARRY, A-FREEZE, A-EXACT)
    * declared `bytes_per_device` WireBytes == the auditor-extracted
      bytes on BOTH tiers, asserted explicitly per geometry
    * distribute's fwd dict carries a scalar int32 "overflow"; stateful
      strategies expose a 1-D f32 carry, return (grad, new_carry), and
      pass the carry through untouched on the accumulate path

  engine (real DPMREngine on the host mesh):
    * dense-oracle agreement on the accumulate (fit) path — EXACT for
      everyone, lossy strategies included, because the accumulate path
      must fall back to an exact reduce
    * SGD-path parity with a2a: bit-level for exact strategies, a
      documented loss tolerance for lossy (error-feedback) ones
    * overflow metric is 0 at default capacity
    * carry init shape/zeros, elastic-reshard reset
    * save()/restore() continues bit-exactly (carry included)

  multi-pod engine (slow, 8 emulated devices in a subprocess): the
  registered compositions train on a real (pod, data, model) mesh —
  fit() parameters match flat a2a, fit_sgd keeps a live namespaced
  carry of the composed length, elastic reshard zeroes it.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace as trace_mod
from repro.analysis.audit import build_contexts
from repro.analysis.contracts import check_strategy
from repro.analysis.wire import wire_total
from repro.api import (DPMREngine, get_strategy, hot_ids_from_corpus,
                       list_strategies)
from repro.configs.base import DPMRConfig
from repro.core import dpmr, reference
from repro.data import get_source, sparse_corpus
from repro.launch.mesh import make_host_mesh
from repro.runtime.elastic import reshard_dpmr_state

# captured at collection time: the built-in registry (other test modules
# register throwaway strategies at RUN time; those are theirs to test)
NAMES = list_strategies()
CONTEXTS = {a.name: a for a in build_contexts(production=False)}
GEOMETRIES = sorted(CONTEXTS)

F = 1 << 12
SPEC = sparse_corpus.CorpusSpec(num_features=F, features_per_sample=16,
                                signal_features=256, seed=0)

# documented SGD-path tolerance vs a2a for strategies that are lossy on
# the HOST mesh (error feedback trades per-step exactness for volume; the
# convergence gates live in test_dpmr / benchmarks). Strategies absent
# here must match a2a's parameters to float tolerance. Compositions are
# exact on a single pod: their lossy leg only exists when outer_shards>1.
SGD_LOSS_RTOL = {"compressed_reduce": 0.05, "topk_reduce": 0.05}


def _batches(batch_size, num_batches):
    src = get_source("zipf_sparse", spec=SPEC, batch_size=batch_size)
    return src.iter_batches(limit=num_batches)


def _cfg(**kw):
    base = dict(num_features=F, max_features_per_sample=16, iterations=2,
                learning_rate=1.0, max_hot=32)
    base.update(kw)
    return DPMRConfig(**base)


# ---------------------------------------------------------------------------
# analytic conformance: every strategy x every geometry, no devices
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced():
    """Per geometry: every strategy's trace + the exact strategies'
    reduce-path signature multisets (the A-EXACT reference set)."""
    out = {}
    for gname, actx in CONTEXTS.items():
        traces = {n: trace_mod.trace_strategy(get_strategy(n), actx.ctx,
                                              actx.axis_sizes)
                  for n in NAMES}
        sigs = {n: trace_mod.signature_multiset(tr.reduce)
                for n, tr in traces.items() if not tr.stateful}
        out[gname] = (traces, sigs)
    return out


@pytest.mark.parametrize("geometry", GEOMETRIES)
@pytest.mark.parametrize("name", NAMES)
def test_contract_rules_pass(name, geometry, traced):
    """Zero findings from the full analysis rule set."""
    traces, sigs = traced[geometry]
    actx = CONTEXTS[geometry]
    _, findings = check_strategy(get_strategy(name), actx.ctx,
                                 actx.axis_sizes, context_name=geometry,
                                 exact_reduce_sigs=sigs, tr=traces[name])
    assert not findings, [f.as_dict() for f in findings]


@pytest.mark.parametrize("geometry", GEOMETRIES)
@pytest.mark.parametrize("name", NAMES)
def test_wire_bytes_equal_auditor_extraction(name, geometry, traced):
    """Declared WireBytes == jaxpr-extracted bytes on BOTH tiers, and the
    outer tier is zero exactly when the geometry has one pod."""
    traces, _ = traced[geometry]
    actx = CONTEXTS[geometry]
    tr = traces[name]
    declared = get_strategy(name).bytes_per_device(actx.ctx)
    extracted = wire_total(tr.distribute + tr.reduce, actx.axis_sizes,
                           actx.ctx.outer_axes)
    assert (int(declared.inner), int(declared.outer)) == \
        (extracted.inner, extracted.outer), (name, geometry)
    if actx.ctx.outer_shards == 1:
        assert extracted.outer == 0
    else:
        assert extracted.outer > 0


@pytest.mark.parametrize("geometry", GEOMETRIES)
@pytest.mark.parametrize("name", NAMES)
def test_overflow_and_carry_structure(name, geometry, traced):
    """fwd["overflow"] is a scalar int32 everywhere; stateful strategies
    carry 1-D f32 state, return (grad, new_carry) with the aval
    preserved, and freeze the carry on the accumulate path."""
    traces, _ = traced[geometry]
    tr = traces[name]
    assert tr.fwd_overflow, (name, geometry)
    if tr.stateful:
        assert tr.carry_1d_f32, (name, geometry)
        assert tr.reduce_pair, (name, geometry)
        assert tr.carry_aval_preserved, (name, geometry)
        assert tr.carry_passthrough, (name, geometry)
    else:
        assert not tr.reduce_pair, (name, geometry)


# ---------------------------------------------------------------------------
# engine conformance: every strategy on the real host mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", NAMES)
def test_dense_oracle_agreement_on_accumulate_path(name):
    """fit() (the accumulate path) matches the numpy GD oracle EXACTLY
    for every strategy — lossy ones must fall back to an exact reduce
    against the frozen carry, so no strategy earns a tolerance here."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution=name, max_hot=16)
    batches = list(_batches(128, 3))
    hot = hot_ids_from_corpus(cfg, batches, mesh)
    eng = DPMREngine(cfg, mesh, hot_ids=hot)
    eng.fit(lambda: iter(batches))
    f = dpmr.padded_features(cfg, mesh)
    oracle = reference.gd_iterations(cfg, batches, cfg.iterations, f)
    theta = reference.engine_table(eng.state)
    np.testing.assert_allclose(theta, np.asarray(oracle), atol=2e-4)
    # the frozen carry never accumulates residual through fit()
    assert float(jnp.abs(eng.state.strat).sum()) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_sgd_path_parity_with_a2a(name):
    """The carry-advancing SGD path: exact strategies reproduce a2a's
    parameters; lossy ones stay within their documented loss tolerance
    (error feedback keeps them convergent, not bit-identical)."""
    mesh = make_host_mesh(1, 1)
    batches = list(_batches(128, 6))
    ref = DPMREngine(_cfg(distribution="a2a"), mesh)
    ref_hist = ref.fit_sgd(iter(batches))
    eng = DPMREngine(_cfg(distribution=name), mesh)
    hist = eng.fit_sgd(iter(batches))
    if name in SGD_LOSS_RTOL:
        a, b = ref_hist[-1]["loss"], hist[-1]["loss"]
        assert abs(a - b) / a < SGD_LOSS_RTOL[name], (name, a, b)
    else:
        np.testing.assert_allclose(np.asarray(ref.state.cold),
                                   np.asarray(eng.state.cold), atol=1e-5)


@pytest.mark.parametrize("name", NAMES)
def test_overflow_metric_zero_at_default_capacity(name):
    mesh = make_host_mesh(1, 1)
    eng = DPMREngine(_cfg(distribution=name), mesh)
    m = eng.train_step(sparse_corpus.make_batch(SPEC, 128, 0))
    assert m["overflow"] == 0, (name, m)


@pytest.mark.parametrize("name", NAMES)
def test_carry_init_and_elastic_reset(name):
    """DPMRState.strat is exactly the strategy's declared carry (or the
    (1,) placeholder), starts at zero, and elastic resharding returns it
    to zero while preserving parameters."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution=name)
    eng = DPMREngine(cfg, mesh)
    ctx = eng.step_fns(128).ctx
    carry = get_strategy(name).init_carry(ctx)
    want = (1,) if carry is None else tuple(carry.shape)
    assert tuple(eng.state.strat.shape) == want, (name, want)
    assert float(jnp.abs(eng.state.strat).sum()) == 0.0
    dirty = eng.state._replace(strat=jnp.ones_like(eng.state.strat))
    fresh = reshard_dpmr_state(dirty, cfg, mesh)
    assert float(jnp.abs(fresh.strat).max()) == 0.0, name
    np.testing.assert_array_equal(np.asarray(fresh.cold),
                                  np.asarray(dirty.cold))


@pytest.mark.parametrize("name", NAMES)
def test_save_restore_bitexact_continuation(name, tmp_path):
    """Interrupt-and-resume == uninterrupted, bit for bit, for EVERY
    strategy (carry included — dropping it would diverge the lossy
    ones)."""
    mesh = make_host_mesh(1, 1)
    cfg = _cfg(distribution=name)
    batches = list(_batches(128, 4))

    full = DPMREngine(cfg, mesh)
    full.fit_sgd(iter(batches))

    part = DPMREngine(cfg, mesh)
    part.fit_sgd(iter(batches[:2]))
    part.save(str(tmp_path))
    resumed = DPMREngine(cfg, mesh)
    resumed.restore(str(tmp_path))
    resumed.fit_sgd(iter(batches[2:]))
    for a, b in zip(full.state, resumed.state, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# multi-pod engine conformance for the compositions (slow, 8 devices)
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_compositions_on_pod_mesh():
    """On a real (2,2,2) (pod,data,model) mesh the registered
    compositions run hier_a2a on ICI and their lossy leg on DCN: fit()
    matches flat a2a exactly (accumulate fallback), fit_sgd banks a live
    carry of the composed length, and elastic reshard zeroes it."""
    body = """
import json
import jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.api import DPMREngine, get_strategy
from repro.configs.base import DPMRConfig
from repro.data import get_source
from repro.runtime.elastic import reshard_dpmr_state

src = get_source("zipf_sparse", batch_size=256, num_features=1<<12,
                 features_per_sample=16, signal_features=256, seed=0)
batches = list(src.iter_batches(limit=3))
base = dict(num_features=1<<12, max_features_per_sample=16, iterations=2,
            learning_rate=1.0, max_hot=32)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
out = {}
ref = DPMREngine(DPMRConfig(distribution="a2a", **base), mesh)
ref.fit(lambda: iter(batches))
for dist in ("hier_a2a+topk", "hier_a2a+int8"):
    # aggressive sparsification so the topk leg actually drops slots
    # (and banks a residual); fit() must match a2a exactly regardless
    cfg = DPMRConfig(distribution=dist, topk_frac=0.05, **base)
    eng = DPMREngine(cfg, mesh)
    eng.fit(lambda: iter(batches))
    assert eng.fns.ctx.outer_axes == ("pod",), eng.fns.ctx
    carry = get_strategy(dist).init_carry(eng.fns.ctx)
    assert carry is not None and carry.ndim == 1
    fit_diff = float(np.max(np.abs(np.asarray(ref.state.cold)
                                   - np.asarray(eng.state.cold))))
    hist = eng.fit_sgd(iter(batches))
    carry_mass = float(jnp.abs(eng.state.strat).sum())
    fresh = reshard_dpmr_state(eng.state, cfg, mesh)
    out[dist] = {
        "fit_diff": fit_diff,
        "carry_len": int(carry.shape[0]),
        "strat_len": int(eng.state.strat.shape[0]),
        "carry_mass": carry_mass,
        "reset_mass": float(jnp.abs(fresh.strat).max()),
        "final_loss": hist[-1]["loss"],
    }
print(json.dumps(out))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for dist, r in out.items():
        assert r["fit_diff"] < 1e-6, (dist, r)
        # the global strat vector stacks one per-device carry per shard
        assert r["strat_len"] == 8 * r["carry_len"], (dist, r)
        assert r["carry_mass"] > 0.0, (dist, r)
        assert r["reset_mass"] == 0.0, (dist, r)
        assert np.isfinite(r["final_loss"]), (dist, r)
