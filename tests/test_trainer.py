"""Trainer invariants: microbatch equivalence, clipping, schedules, AdamW."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ParallelConfig, TrainConfig
from repro.data.pipeline import LMDataConfig, LMDataset
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.optim import optimizers, schedules
from repro.train import trainer


def _setup(arch="yi-6b", micro=1, opt="adamw"):
    cfg = registry.smoke_config(arch)
    spec = registry.get_spec(arch)
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=100,
                     optimizer=opt, grad_clip=1.0)
    pc = ParallelConfig(microbatches=micro)
    mesh = make_host_mesh(1, 1)
    return cfg, spec, tc, pc, mesh


def test_microbatch_equivalence():
    """k=1 and k=4 grad accumulation produce the same update."""
    outs = {}
    for k in (1, 4):
        cfg, spec, tc, pc, mesh = _setup(micro=k)
        with jax.set_mesh(mesh):
            state = trainer.init_state(spec, cfg, tc, pc,
                                       jax.random.PRNGKey(0))
            step = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))
            ds = LMDataset(LMDataConfig(cfg.vocab_size, 16, 8))
            state, m = step(state, jax.tree.map(jnp.asarray, ds.batch(0)))
        outs[k] = (np.asarray(
            jax.tree.leaves(state["params"])[0]), float(m["loss"]))
    np.testing.assert_allclose(outs[1][1], outs[4][1], rtol=1e-5)
    np.testing.assert_allclose(outs[1][0], outs[4][0], rtol=2e-4, atol=2e-6)


def test_grad_clip():
    g = {"a": jnp.full((10,), 100.0), "b": jnp.full((10,), -100.0)}
    clipped, norm = optimizers.clip_by_global_norm(g, 1.0)
    assert float(norm) > 400
    cn = optimizers.global_norm(clipped)
    np.testing.assert_allclose(float(cn), 1.0, rtol=1e-5)


def test_warmup_cosine_schedule():
    fn = schedules.warmup_cosine(1.0, 10, 100, final_frac=0.1)
    assert float(fn(jnp.int32(0))) == 0.0
    np.testing.assert_allclose(float(fn(jnp.int32(10))), 1.0, rtol=1e-6)
    assert float(fn(jnp.int32(55))) < 1.0
    np.testing.assert_allclose(float(fn(jnp.int32(100))), 0.1, rtol=1e-5)


def test_adamw_matches_reference():
    """One AdamW step vs a hand-rolled numpy reference."""
    tc = TrainConfig(learning_rate=0.1, weight_decay=0.01, beta1=0.9,
                     beta2=0.999)
    opt = optimizers.get_optimizer("adamw")
    p = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    g = {"w": jnp.asarray([0.1, 0.2, -0.3])}
    state = opt.init(p, "float32")
    new_p, new_state = opt.update(g, state, p, 0.1, tc)

    m = 0.1 * np.asarray(g["w"])
    v = 0.001 * np.asarray(g["w"]) ** 2
    mh = m / (1 - 0.9)
    vh = v / (1 - 0.999)
    want = np.asarray(p["w"]) - 0.1 * (
        mh / (np.sqrt(vh) + 1e-8) + 0.01 * np.asarray(p["w"]))
    np.testing.assert_allclose(np.asarray(new_p["w"]), want, rtol=1e-5)
    assert int(new_state["count"]) == 1


def test_sgd_and_momentum_update_directions():
    tc = TrainConfig(learning_rate=1.0, beta1=0.9)
    p = {"w": jnp.ones((4,))}
    g = {"w": jnp.ones((4,))}
    for name in ("sgd", "momentum"):
        opt = optimizers.get_optimizer(name)
        st = opt.init(p, "float32")
        np_, _ = opt.update(g, st, p, 0.5, tc)
        assert float(np_["w"][0]) < 1.0


def test_deterministic_data_pipeline():
    ds = LMDataset(LMDataConfig(100, 8, 4, seed=3))
    b1 = ds.batch(7)
    b2 = ds.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = ds.batch(8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_loss_decreases_over_training():
    cfg, spec, tc, pc, mesh = _setup(arch="granite-8b")
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, tc, pc, jax.random.PRNGKey(0))
        step = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))
        ds = LMDataset(LMDataConfig(cfg.vocab_size, 32, 8))
        losses = []
        for i in range(25):
            state, m = step(state, jax.tree.map(jnp.asarray, ds.batch(i)))
            losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses
