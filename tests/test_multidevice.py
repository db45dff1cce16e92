"""Multi-device semantics, run in subprocesses with 8 fake host devices
(XLA_FLAGS can't change after jax initializes in the main pytest process).

Covers: DP/TP/FSDP mesh-layout invariance of training, DPMR sparse-face
multi-shard == single-shard, the explicit DPMR-dense (FSDP) linear vs plain
matmul, and cross-pod compressed training.
"""
import json
import os
import subprocess
import sys

import pytest

# every test here re-inits jax in a subprocess with 8 fake devices — minutes
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout: int = 600) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


COMMON = """
import json
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_host_mesh, make_mesh
"""


def test_training_invariant_to_mesh_layout():
    """Same model, same data: loss identical on (1,1), (4,2), (2,4)."""
    out = run_py(COMMON + """
from repro.models import registry
from repro.train import trainer
from repro.configs.base import TrainConfig, ParallelConfig
from repro.data.pipeline import LMDataset, LMDataConfig

cfg = registry.smoke_config("granite-8b")
spec = registry.get_spec("granite-8b")
tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=10)
losses = {}
for (d, m) in [(1,1),(4,2),(2,4)]:
    mesh = make_host_mesh(d, m)
    pc = ParallelConfig(microbatches=2)
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, tc, pc, jax.random.PRNGKey(0))
        step = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))
        ds = LMDataset(LMDataConfig(cfg.vocab_size, 16, 8))
        for i in range(4):
            state, met = step(state, jax.tree.map(jnp.asarray, ds.batch(i)))
    losses[f"{d}x{m}"] = float(met["loss"])
print(json.dumps(losses))
""")
    vals = list(out.values())
    assert max(vals) - min(vals) < 2e-3, out


def test_dpmr_multi_shard_matches_single():
    out = run_py(COMMON + """
from repro.api import DPMREngine, hot_ids_from_corpus
from repro.configs.base import DPMRConfig
from repro.data import get_source

src = get_source("zipf_sparse", batch_size=256, num_features=1<<12,
                 features_per_sample=16, signal_features=256, seed=0)
cfg = DPMRConfig(num_features=1<<12, max_features_per_sample=16,
                 iterations=2, learning_rate=1.0, max_hot=32)
batches = list(src.iter_batches(limit=4))
colds = {}
for (d, m) in [(1,1),(4,2)]:
    mesh = make_host_mesh(d, m)
    hot = hot_ids_from_corpus(cfg, batches, mesh)
    eng = DPMREngine(cfg, mesh, hot_ids=hot)
    eng.fit(lambda: iter(batches))
    colds[f"{d}x{m}"] = np.asarray(eng.state.cold)
diff = float(np.max(np.abs(colds["1x1"] - colds["4x2"])))
print(json.dumps({"max_diff": diff}))
""")
    assert out["max_diff"] < 1e-6, out


def test_hier_and_compressed_strategies_on_pod_mesh():
    """(2,2,2) (pod,data,model) mesh: hier_a2a's two-level exchange
    produces the same parameters as flat a2a (float-order tolerance), and
    compressed_reduce trains with a live error-feedback carry."""
    out = run_py(COMMON + """
from repro.api import DPMREngine
from repro.configs.base import DPMRConfig
from repro.data import get_source

src = get_source("zipf_sparse", batch_size=256, num_features=1<<12,
                 features_per_sample=16, signal_features=256, seed=0)
batches = list(src.iter_batches(limit=3))
base = dict(num_features=1<<12, max_features_per_sample=16, iterations=2,
            learning_rate=1.0, max_hot=32)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
colds = {}
for dist in ("a2a", "hier_a2a"):
    eng = DPMREngine(DPMRConfig(distribution=dist, **base), mesh)
    eng.fit(lambda: iter(batches))
    assert eng.fns.ctx.outer_axes == ("pod",), eng.fns.ctx
    colds[dist] = np.asarray(eng.state.cold)
sgd = {}
hist = None
for dist in ("a2a", "compressed_reduce"):
    eng = DPMREngine(DPMRConfig(distribution=dist, **base), mesh)
    hist = eng.fit_sgd(iter(batches))
    sgd[dist] = eng
print(json.dumps({
    "max_diff": float(np.max(np.abs(colds["a2a"] - colds["hier_a2a"]))),
    "comp_final_loss": hist[-1]["loss"],
    "comp_vs_a2a": float(np.max(np.abs(
        np.asarray(sgd["compressed_reduce"].state.cold)
        - np.asarray(sgd["a2a"].state.cold)))),
    "carry_nonzero": bool(np.abs(np.asarray(
        sgd["compressed_reduce"].state.strat)).sum() > 0)}))
""")
    assert out["max_diff"] < 1e-5, out          # exact up to float order
    import math
    assert math.isfinite(out["comp_final_loss"]), out
    assert out["carry_nonzero"] is True, out
    assert out["comp_vs_a2a"] < 0.05, out       # quantized but tracking


def test_overlap_and_topk_strategies_on_pod_mesh():
    """(2,2,2) (pod,data,model) mesh: overlap_a2a's micro-chunked exchange
    is BIT-IDENTICAL to flat a2a (same losses, same parameters — no
    float-order tolerance: element routing is unchanged, only the
    collective schedule differs), and topk_reduce at a sparsifying
    fraction trains with a live error-feedback residual that tracks a2a."""
    out = run_py(COMMON + """
from repro.api import DPMREngine
from repro.configs.base import DPMRConfig
from repro.data import get_source

src = get_source("zipf_sparse", batch_size=256, num_features=1<<12,
                 features_per_sample=16, signal_features=256, seed=0)
batches = list(src.iter_batches(limit=3))
base = dict(num_features=1<<12, max_features_per_sample=16, iterations=2,
            learning_rate=1.0, max_hot=32)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
out = {}
state = {}
for dist in ("a2a", "overlap_a2a"):
    eng = DPMREngine(DPMRConfig(distribution=dist, **base), mesh)
    hist = eng.fit_sgd(iter(batches))
    out[f"losses_{dist}"] = [h["loss"] for h in hist]
    state[dist] = eng
topk = DPMREngine(DPMRConfig(distribution="topk_reduce", topk_frac=0.05,
                             **base), mesh)
topk.fit_sgd(iter(batches))
a = np.asarray(state["a2a"].state.cold)
print(json.dumps({
    "overlap_bit_identical": bool(np.array_equal(
        a, np.asarray(state["overlap_a2a"].state.cold))),
    "losses_equal": out["losses_a2a"] == out["losses_overlap_a2a"],
    "topk_carry_nonzero": bool(np.abs(np.asarray(
        topk.state.strat)).sum() > 0),
    "topk_vs_a2a": float(np.max(np.abs(
        a - np.asarray(topk.state.cold))))}))
""")
    assert out["overlap_bit_identical"] is True, out
    assert out["losses_equal"] is True, out
    assert out["topk_carry_nonzero"] is True, out
    assert out["topk_vs_a2a"] < 0.05, out       # sparsified but tracking


def test_explicit_fsdp_linear_matches_matmul():
    """core.fsdp.dpmr_dense_linear (all_gather/psum_scatter staging) ==
    plain x @ W, forward AND backward."""
    out = run_py(COMMON + """
from jax.sharding import PartitionSpec as P
from repro.core.fsdp import dpmr_dense_linear

mesh = make_host_mesh(8, 1)
rng = np.random.default_rng(0)
D, F, B = 32, 24, 16
w = jnp.asarray(rng.normal(size=(D, F)), jnp.float32)
x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)

def staged(w, x):
    f = jax.shard_map(lambda ws, xs: dpmr_dense_linear(ws, xs, "data"),
                         mesh=mesh, in_specs=(P("data", None), P()),
                         out_specs=P(), check_vma=False)
    return f(w, x)

def loss_staged(w, x): return jnp.sum(jnp.sin(staged(w, x)))
def loss_plain(w, x): return jnp.sum(jnp.sin(x @ w))

with jax.set_mesh(mesh):
    y1 = staged(w, x)
    g1 = jax.grad(loss_staged)(w, x)
y2 = x @ w
g2 = jax.grad(loss_plain)(w, x)
print(json.dumps({
  "fwd": float(jnp.max(jnp.abs(y1 - y2))),
  "bwd": float(jnp.max(jnp.abs(g1 - g2)))}))
""")
    assert out["fwd"] < 1e-4 and out["bwd"] < 1e-4, out


def test_cross_pod_compressed_training_converges():
    """Compressed cross-pod grads: loss tracks uncompressed within 5%."""
    out = run_py(COMMON + """
from repro.models import registry
from repro.train import trainer
from repro.configs.base import TrainConfig, ParallelConfig
from repro.data.pipeline import LMDataset, LMDataConfig

cfg = registry.smoke_config("yi-6b")
spec = registry.get_spec("yi-6b")
tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=20)

def run(compress):
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    pc = ParallelConfig(compress_pod_grads=compress)
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, tc, pc, jax.random.PRNGKey(0))
        step = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))
        ds = LMDataset(LMDataConfig(cfg.vocab_size, 16, 8))
        for i in range(12):
            state, m = step(state, jax.tree.map(jnp.asarray, ds.batch(i)))
    return float(m["loss"])

print(json.dumps({"plain": run(False), "compressed": run(True)}))
""")
    assert abs(out["plain"] - out["compressed"]) / out["plain"] < 0.05, out


def test_context_parallel_attention_matches_blocked():
    """CP attention (q sequence-sharded, kv-only gather) == blocked oracle,
    forward and gradient, on a sharded mesh."""
    out = run_py(COMMON + """
from repro.models import layers
mesh = make_host_mesh(2, 4)
rng = np.random.default_rng(0)
b, s, h, kh, d = 2, 64, 4, 2, 16
q = jnp.asarray(rng.normal(size=(b,s,h,d)), jnp.float32)
k = jnp.asarray(rng.normal(size=(b,s,kh,d)), jnp.float32)
v = jnp.asarray(rng.normal(size=(b,s,kh,d)), jnp.float32)
res = {}
with jax.set_mesh(mesh):
    for causal, window in [(True,0),(True,16),(False,0)]:
        cp = jax.jit(lambda q,k,v: layers.context_parallel_attention(
            q,k,v,causal=causal,window=window,kv_block=16))(q,k,v)
        ref = layers.blocked_causal_attention(
            q,k,v,window=window,q_block=16,kv_block=16) if causal \\
            else layers._bidirectional_blocked(q,k,v,q_block=16,kv_block=16)
        res[f"{causal}_{window}"] = float(jnp.max(jnp.abs(cp-ref)))
    g = jax.jit(jax.grad(lambda q,k,v: jnp.sum(jnp.sin(
        layers.context_parallel_attention(q,k,v)))))(q,k,v)
    res["grad_finite"] = bool(jnp.all(jnp.isfinite(g)))
print(json.dumps(res))
""")
    assert out.pop("grad_finite") is True
    assert all(v < 1e-5 for v in out.values()), out


def test_cp_train_step_matches_auto():
    """Training with attn_mode=cp computes the same loss as attn_mode=auto."""
    out = run_py(COMMON + """
from repro.models import registry
from repro.train import trainer
from repro.configs.base import TrainConfig, ParallelConfig
from repro.data.pipeline import LMDataset, LMDataConfig

cfg = registry.smoke_config("granite-8b")
spec = registry.get_spec("granite-8b")
tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=10)
res = {}
for mode in ("auto", "cp"):
    mesh = make_host_mesh(2, 4)
    pc = ParallelConfig(attn_mode=mode)
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, tc, pc, jax.random.PRNGKey(0))
        step = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))
        ds = LMDataset(LMDataConfig(cfg.vocab_size, 16, 8))
        for i in range(3):
            state, m = step(state, jax.tree.map(jnp.asarray, ds.batch(i)))
    res[mode] = float(m["loss"])
print(json.dumps(res))
""")
    assert abs(out["auto"] - out["cp"]) < 2e-3, out


def test_multipod_mesh_trains():
    """(2,2,2) pod mesh: one train step on every family that fits."""
    out = run_py(COMMON + """
from repro.models import registry
from repro.train import trainer
from repro.configs.base import TrainConfig, ParallelConfig
from repro.data.pipeline import LMDataset, LMDataConfig, encdec_batch

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
res = {}
for arch in ["granite-8b", "mixtral-8x22b", "zamba2-2.7b", "whisper-small"]:
    cfg = registry.smoke_config(arch)
    spec = registry.get_spec(arch)
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=5)
    pc = ParallelConfig()
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, tc, pc, jax.random.PRNGKey(0))
        step = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))
        ds = LMDataset(LMDataConfig(cfg.vocab_size, 16, 8))
        b = ds.batch(0)
        if cfg.family == "encdec":
            b = encdec_batch(ds, 0, cfg.d_model)
        state, m = step(state, jax.tree.map(jnp.asarray, b))
    res[arch] = float(m["loss"])
print(json.dumps(res))
""", timeout=900)
    import math
    assert all(math.isfinite(v) for v in out.values()), out
