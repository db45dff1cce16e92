"""End-to-end behaviour tests for the paper's system."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DPMREngine, hot_ids_from_corpus
from repro.configs import ARCH_IDS, SHAPES
from repro.configs.base import DPMRConfig
from repro.data import get_source
from repro.launch.mesh import make_host_mesh
from repro.models import registry


@pytest.mark.slow
def test_paper_pipeline_end_to_end():
    """Algorithm 8 (train) + Algorithm 9 (classify): the full loop improves
    F over the majority-class baseline — the paper's Fig. 1 behaviour."""
    src = get_source("zipf_sparse", batch_size=512, num_features=1 << 14,
                     features_per_sample=32, signal_features=512, seed=0)
    cfg = DPMRConfig(num_features=1 << 14, max_features_per_sample=32,
                     iterations=8, learning_rate=2.0, max_hot=64,
                     optimizer="adagrad")
    mesh = make_host_mesh(1, 1)
    train = lambda: src.iter_batches(limit=8)
    test = list(src.iter_batches(start=50, limit=2))
    hot = hot_ids_from_corpus(cfg, train(), mesh)
    evals = []

    def ev(engine):
        m = engine.evaluate(test)
        evals.append(m)
        return m

    DPMREngine(cfg, mesh, hot_ids=hot).fit(train, eval_fn=ev)
    # converging: last F beats first F, and both classes predicted
    assert evals[-1]["f_avg"] > evals[0]["f_avg"]
    assert evals[-1]["f_pos"] > 0.6 and evals[-1]["f_neg"] > 0.3, evals[-1]


def test_all_archs_registered_with_shapes():
    """Deliverable (f): 10 archs x shape sets = the assigned 40-cell grid."""
    assert len(ARCH_IDS) == 10
    cells = 0
    for arch in ARCH_IDS:
        spec = registry.get_spec(arch)
        assert spec.cfg.name == arch
        assert set(spec.supported_shapes) <= set(SHAPES)
        cells += 4  # the assignment defines 4 shape cells per arch
        if len(spec.supported_shapes) < 4:
            assert spec.skip_reason  # skips must be justified
    assert cells == 40


def test_serve_greedy_decode_runs():
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.train import serve, trainer

    mesh = make_host_mesh(1, 1)
    cfg = registry.smoke_config("yi-6b")
    spec = registry.get_spec("yi-6b")
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, TrainConfig(optimizer="sgd"),
                                   ParallelConfig(), jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((2, 8), jnp.int32)}
        toks = serve.greedy_decode(spec, cfg, state["params"], batch, 5,
                                   ParallelConfig(seq_shard=False))
    assert toks.shape == (2, 5)
    assert jnp.all((toks >= 0) & (toks < cfg.vocab_size))


def test_production_mesh_shapes():
    """make_production_mesh is a function (no import-time device usage)."""
    import inspect

    from repro.launch import mesh as mesh_mod

    assert inspect.isfunction(mesh_mod.make_production_mesh)
    src = inspect.getsource(mesh_mod)
    assert "(2, 16, 16)" in src and "(16, 16)" in src


def test_dryrun_collective_parser():
    from repro.launch.dryrun import _collectives_from_hlo

    hlo = """
  %ag = bf16[16,1024,512]{2,1,0} all-gather(%p), replica_groups=[16,16]<=[256]
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %a2a = f32[8,64]{1,0} all-to-all(%y), replica_groups=[2,128]<=[256]
  %other = f32[2] add(%a, %b)
"""
    cols = _collectives_from_hlo(hlo)
    kinds = sorted(c["op"] for c in cols)
    assert kinds == ["all-gather", "all-reduce", "all-to-all"]
    ag = [c for c in cols if c["op"] == "all-gather"][0]
    assert ag["bytes"] == 16 * 1024 * 512 * 2
    assert ag["group_size"] == 16
    ar = [c for c in cols if c["op"] == "all-reduce"][0]
    assert ar["group_size"] == 4


def test_hot_sharding_reduces_overflow():
    """Paper §4 claim: splitting out the Zipf head bounds the shuffle skew.

    Ownership is contiguous-block, so a Zipf head concentrated in one
    owner's block overflows a tight capacity; masking the head (replication
    = the paper's sub-feature sharding) makes the same capacity suffice."""
    from repro.core import hot_sharding, sparse

    rng = np.random.default_rng(3)
    f, p = 4096, 8
    block, cap = f // p, 24
    # Zipf-ish head: 60% of hits on 16 ids inside ONE owner block
    head = rng.integers(0, block // 4, size=600).astype(np.int32) % 16
    tail = rng.integers(0, f, size=400).astype(np.int32)
    ids = jnp.asarray(np.concatenate([head, tail]))

    hot = jnp.asarray(hot_sharding.select_hot(ids, threshold=0.01,
                                              max_hot=32))
    _, _, cold = hot_sharding.split_hot(ids, hot)

    r_no = sparse.route_build(ids, p, block, cap)
    r_hot = sparse.route_build(cold, p, block, cap)
    assert int(r_no.overflow) > int(r_hot.overflow), (
        int(r_no.overflow), int(r_hot.overflow))
    # and the load imbalance diagnostic improves
    imb_no = float(hot_sharding.load_imbalance(ids, p, block))
    imb_hot = float(hot_sharding.load_imbalance(cold, p, block))
    assert imb_hot <= imb_no
