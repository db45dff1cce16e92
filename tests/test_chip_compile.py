"""Compiles for a described TPU v5e: what the chip's compiler would refuse.

Nothing here runs on a chip. The TPU compiler compiles for a `v5e:2x2`
topology that is described, not attached, so these tests catch a kernel
block that breaks the tiling rules, a kernel or step that outgrows the
device's memory, or a sharded step that loses its exchange — at the
shapes `chip_smoke.py` runs, without chip time. The topology is described
inside a module fixture (the TPU library may be loaded by one process at
a time, so never at import time); where it cannot be described the tests
skip.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import DPMRConfig
from repro.core import dpmr, hot_sharding
from repro.kernels import segment_sum, select_pack, sigmoid_grad

GiB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _mesh(topo, n: int):
    return Mesh(np.array(topo.devices[:n]).reshape(1, n), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _one(topo, shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=jax.sharding.SingleDeviceSharding(
            topo.devices[0]))


# the shapes of chip_smoke.py: batch 4096 x K 64 per chip, N = P * cap
# (262,144 on one chip, 65,536 per chip on four), select_pack at P = 4 up
# to MAX_CAPACITY with k = cap / 4
KERNELS = {
    "sigmoid_grad_4096x64": lambda t: (
        lambda v, th, y: sigmoid_grad.sigmoid_grad(v, th, y),
        _one(t, (4096, 64), jnp.float32), _one(t, (4096, 64), jnp.float32),
        _one(t, (4096,), jnp.int32)),
    "segment_sum_262144": lambda t: (
        lambda i, g: segment_sum.segment_sum_sorted(i, g),
        _one(t, (262144,), jnp.int32), _one(t, (262144,), jnp.float32)),
    "segment_sum_65536": lambda t: (
        lambda i, g: segment_sum.segment_sum_sorted(i, g),
        _one(t, (65536,), jnp.int32), _one(t, (65536,), jnp.float32)),
    "select_pack_p4_max_capacity": lambda t: (
        lambda s, i, c: select_pack.select_pack(
            s, i, c, k=select_pack.MAX_CAPACITY // 4),
        *(_one(t, (4, select_pack.MAX_CAPACITY), dt)
          for dt in (jnp.float32, jnp.int32, jnp.float32))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(topo, name):
    fn, *avals = KERNELS[name](topo)
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text, name


def _train_step(topo, n_chips: int, impl: str):
    """The compiled 2^27-feature a2a/adagrad train_step of chip_smoke.py
    on `n_chips` described chips."""
    cfg = DPMRConfig(num_features=1 << 27, max_features_per_sample=64,
                     learning_rate=2.0, max_hot=512, optimizer="adagrad",
                     distribution="a2a", kernel_impl=impl)
    mesh = _mesh(topo, n_chips)
    axes = mesh.axis_names
    shard, rep = NamedSharding(mesh, P(axes)), NamedSharding(mesh, P())
    f = dpmr.padded_features(cfg, mesh)
    carry = dpmr.num_shards(mesh) * dpmr.strategy_carry_len(cfg, mesh)

    def s(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = dpmr.DPMRState(
        cold=s((f,), jnp.float32, shard), hot=s((512,), jnp.float32, rep),
        hot_ids=s((512,), jnp.int32, rep),
        cold_acc=s((f,), jnp.float32, shard),
        hot_acc=s((512,), jnp.float32, rep), step=s((), jnp.int32, rep),
        strat=s((carry,), jnp.float32, shard))
    batch = {"ids": s((4096, 64), jnp.int32, shard),
             "vals": s((4096, 64), jnp.float32, shard),
             "labels": s((4096,), jnp.int32, shard)}
    with jax.set_mesh(mesh):
        fns = dpmr.make_step_fns(cfg, mesh, 4096)
        return fns.train_step.lower(state, batch).compile()


# temp_size_in_bytes of the one-chip step below when `split_hot` was a
# binary search (searchsorted), read for the described v5e (PERF.md)
SEARCH_SPLIT_TEMP = 538_870_784


@pytest.fixture(scope="module")
def one_chip_step(topo):
    return _train_step(topo, 1, "xla")


def test_train_step_one_chip_fits(one_chip_step):
    """One chip holds the whole 2^27 table: cold + cold_acc are 1 GiB of
    arguments, and the step's temporaries stay well inside 16 GB."""
    mem = one_chip_step.memory_analysis()
    assert 2 * (1 << 27) * 4 <= mem.argument_size_in_bytes < 1.1 * GiB
    assert mem.temp_size_in_bytes < 4 * GiB


def test_train_step_split_stores_no_compare(one_chip_step):
    """The split's (512, 262144) compare stays inside its reduction:
    the step needs no more temporaries than with the binary search."""
    mem = one_chip_step.memory_analysis()
    assert mem.temp_size_in_bytes <= SEARCH_SPLIT_TEMP + 4 * (1 << 20)


def test_split_hot_compiles_without_search(topo):
    """At 262,144 ids against 512 hot ids the split is one fused
    compare-and-reduce: no loop, no gather."""
    text = jax.jit(hot_sharding.split_hot).lower(
        _one(topo, (262144,), jnp.int32),
        _one(topo, (512,), jnp.int32)).compile().as_text()
    assert not re.search(r"\s(while|gather)\(", text)


def test_train_step_four_chips_exchanges(topo):
    """On the described 2x2 host (mesh 1x4, P = 4) each chip holds a
    quarter of the table, the a2a exchange is in the program as
    all-to-alls, and the Pallas path carries its kernels."""
    compiled = _train_step(topo, 4, "pallas")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 0.3 * GiB
    text = compiled.as_text()
    assert "all-to-all" in text
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [1, 8])
def test_predict_head_sums_in_fixed_order(topo, rows):
    """The predict head (shared by the device predict and the serving hot
    cache) compiles to explicit adds, not to a `reduce` whose order the
    compiler picks per program: its tiling differs with the batch size."""
    avals = [_one(topo, (rows, 64), jnp.float32)] * 2
    text = jax.jit(dpmr.predict_probs).lower(*avals).compile().as_text()
    adds = [l for l in text.splitlines() if " add(" in l]
    for width in (32, 16, 8, 4, 2, 1):       # 64 -> 1 by halves
        assert any(f"f32[{rows},{width}]" in l for l in adds), width
