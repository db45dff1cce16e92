"""Per-arch smoke tests: reduced same-family configs, one forward/train step
on CPU, asserting output shapes + finiteness; plus prefill->decode vs
teacher-forced forward consistency (the serve path computes the same math).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS
from repro.configs.base import ParallelConfig, TrainConfig
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.models.common import embed_init_scale
from repro.sharding import init_from_defs
from repro.train import trainer


def _params(spec, cfg, key=0):
    return init_from_defs(spec.defs(cfg), jax.random.PRNGKey(key),
                          scale_fn=embed_init_scale)


def _batch(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(b, s)), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, s, cfg.d_model)), jnp.float32)
    batch["labels"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(b, s)), jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = registry.smoke_config(arch)
    spec = registry.get_spec(arch)
    params = _params(spec, cfg)
    batch = _batch(cfg)
    logits, aux = spec.forward(params, batch, cfg, None)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits)), arch
    assert jnp.isfinite(aux)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nans(arch):
    cfg = registry.smoke_config(arch)
    spec = registry.get_spec(arch)
    mesh = make_host_mesh(1, 1)
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=2, total_steps=10)
    pc = ParallelConfig(microbatches=1)
    with jax.set_mesh(mesh):
        state = trainer.init_state(spec, cfg, tc, pc, jax.random.PRNGKey(0))
        step = jax.jit(trainer.make_train_step(spec, cfg, tc, pc, mesh))
        b = _batch(cfg, b=4, s=16)
        state, m = step(state, b)
        state, m2 = step(state, _batch(cfg, b=4, s=16, seed=1))
    assert np.isfinite(float(m["loss"])) and np.isfinite(float(m2["loss"]))
    for leaf in jax.tree.leaves(state["params"]):
        assert jnp.all(jnp.isfinite(leaf))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    """prefill(S) + decode(2 tokens) must reproduce the teacher-forced
    logits at the same positions (serve path == train math)."""
    cfg = registry.smoke_config(arch)
    spec = registry.get_spec(arch)
    params = _params(spec, cfg)
    b, s = 2, 12
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, size=(b, s + 2)).astype(np.int32)
    full_batch = {"tokens": jnp.asarray(toks)}
    pre_batch = {"tokens": jnp.asarray(toks[:, :s])}
    if cfg.family == "encdec":
        frames = rng.normal(size=(b, s, cfg.d_model)).astype(np.float32)
        # teacher-forced forward must see the SAME encoder input
        full_batch["frames"] = jnp.asarray(frames)
        pre_batch["frames"] = jnp.asarray(frames)

    parallel = ParallelConfig(seq_shard=False, remat="none")
    logits_full, _ = spec.forward(params, full_batch, cfg, parallel)
    logits_p, cache = spec.prefill(params, pre_batch, cfg, parallel)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, 0]), np.asarray(logits_full[:, s - 1]),
        rtol=2e-2, atol=2e-2)

    logits_d1, cache = spec.decode_step(
        params, cache, jnp.asarray(toks[:, s:s + 1]), cfg)
    np.testing.assert_allclose(
        np.asarray(logits_d1[:, 0]), np.asarray(logits_full[:, s]),
        rtol=2e-2, atol=2e-2)
    logits_d2, _ = spec.decode_step(
        params, cache, jnp.asarray(toks[:, s + 1:s + 2]), cfg)
    np.testing.assert_allclose(
        np.asarray(logits_d2[:, 0]), np.asarray(logits_full[:, s + 1]),
        rtol=2e-2, atol=2e-2)


def test_swa_matches_full_attention_within_window():
    """Mixtral's SWA must equal full attention when S <= window."""
    from repro.models import layers

    rng = np.random.default_rng(0)
    b, s, h, kh, d = 2, 16, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
    full = layers.blocked_causal_attention(q, k, v, q_block=8, kv_block=8)
    swa = layers.blocked_causal_attention(q, k, v, window=s, q_block=8,
                                          kv_block=8)
    np.testing.assert_allclose(np.asarray(full), np.asarray(swa),
                               rtol=1e-5, atol=1e-5)


def test_swa_restricts_receptive_field():
    """Changing a token outside the window must not change the output."""
    from repro.models import layers

    rng = np.random.default_rng(1)
    b, s, h, d, w = 1, 32, 2, 8, 4
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    out1 = layers.blocked_causal_attention(q, k, v, window=w, q_block=8,
                                           kv_block=8)
    k2 = k.at[:, 0].add(10.0)   # outside the window of positions >= w
    v2 = v.at[:, 0].add(10.0)
    out2 = layers.blocked_causal_attention(q, k2, v2, window=w, q_block=8,
                                           kv_block=8)
    np.testing.assert_allclose(np.asarray(out1[:, w:]),
                               np.asarray(out2[:, w:]), rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(out1[:, 0]), np.asarray(out2[:, 0]))


def test_blocked_attention_matches_reference():
    from repro.kernels import ref
    from repro.models import layers

    rng = np.random.default_rng(2)
    for (b, s, h, kh, d) in [(2, 64, 4, 2, 16), (1, 48, 3, 1, 8)]:
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
        blocked = layers.blocked_causal_attention(q, k, v, q_block=16,
                                                  kv_block=16)
        oracle = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(blocked), np.asarray(oracle),
                                   rtol=2e-5, atol=2e-5)


def test_masked_scan_attention_matches_triangular():
    from repro.models.layers import (_masked_scan_attention,
                                     _triangular_attention)

    rng = np.random.default_rng(4)
    b, s, h, d = 1, 64, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    a = _triangular_attention(q, k, v, 16, 16, d ** -0.5)
    m = _masked_scan_attention(q, k, v, 16, 16, d ** -0.5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(m), rtol=2e-5,
                               atol=2e-5)
