"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("b,k", [(8, 16), (64, 32), (128, 64), (33, 7)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_sigmoid_grad_sweep(b, k, dtype):
    rng = np.random.default_rng(b * 100 + k)
    vals = jnp.asarray(rng.normal(size=(b, k)).astype(dtype))
    theta = jnp.asarray(rng.normal(size=(b, k)).astype(dtype))
    y = jnp.asarray(rng.integers(0, 2, size=(b,)).astype(np.int32))
    g0, p0, n0 = ops.sigmoid_grad(vals, theta, y, impl="jnp")
    g1, p1, n1 = ops.sigmoid_grad(vals, theta, y, impl="pallas_interpret",
                                  block_b=16)
    tol = 1e-5 if dtype == np.float32 else 2e-3
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), atol=tol)
    np.testing.assert_allclose(np.asarray(p0), np.asarray(p1), atol=tol)
    np.testing.assert_allclose(np.asarray(n0), np.asarray(n1), atol=tol)


@pytest.mark.parametrize("n,block", [(64, 16), (256, 32), (256, 256),
                                     (1024, 128), (100, 100)])
@pytest.mark.parametrize("nruns", [3, 40])
def test_segment_sum_sweep(n, block, nruns):
    rng = np.random.default_rng(n + nruns)
    ids = np.sort(rng.integers(0, nruns, size=n - n // 8)).astype(np.int32)
    ids = np.concatenate([ids, np.full(n // 8, -1, np.int32)])
    # padding must sort LAST: engine sorts with key int32max; emulate
    ids = np.concatenate([np.sort(ids[ids >= 0]), ids[ids < 0]])
    g = rng.normal(size=(n,)).astype(np.float32)
    r0 = ops.segment_sum_sorted(jnp.asarray(ids), jnp.asarray(g), impl="jnp")
    r1 = ops.segment_sum_sorted(jnp.asarray(ids), jnp.asarray(g),
                                impl="pallas_interpret", block=block)
    np.testing.assert_allclose(np.asarray(r0), np.asarray(r1), atol=1e-5)
    # totals preserved
    np.testing.assert_allclose(float(jnp.sum(r1)), float(np.sum(g[ids >= 0])),
                               atol=1e-4)


def test_segment_sum_run_spanning_blocks():
    """A single run spanning 4 blocks must emit exactly one total."""
    n, block = 64, 16
    ids = jnp.zeros((n,), jnp.int32)
    g = jnp.ones((n,), jnp.float32)
    out = ops.segment_sum_sorted(ids, g, impl="pallas_interpret", block=block)
    ref_out = ops.segment_sum_sorted(ids, g, impl="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out))
    assert float(out[-1]) == n
    assert float(jnp.sum(out)) == n


@pytest.mark.parametrize("shapes", [
    (1, 32, 2, 2, 8), (2, 64, 4, 2, 16), (2, 128, 8, 1, 32),
    (1, 64, 6, 3, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(shapes, dtype, causal):
    b, s, h, kh, d = shapes
    rng = np.random.default_rng(sum(shapes))
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kh, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kh, d)), dtype)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal)
    o_ker = ops.flash_attention(q, k, v, causal=causal,
                                impl="pallas_interpret",
                                block_q=16, block_k=16)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o_ref, np.float32), np.asarray(o_ker, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_gqa_group_mapping():
    """GQA: each q head must attend to ITS kv head, not head 0."""
    b, s, h, kh, d = 1, 16, 4, 2, 8
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
    out = ops.flash_attention(q, k, v, impl="pallas_interpret",
                              block_q=8, block_k=8)
    # head 3 belongs to kv head 1: zeroing kv head 0 must not change it
    k0 = k.at[:, :, 0].set(0.0)
    v0 = v.at[:, :, 0].set(0.0)
    out2 = ops.flash_attention(q, k0, v0, impl="pallas_interpret",
                               block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out[:, :, 3]),
                               np.asarray(out2[:, :, 3]), atol=1e-6)
    assert not np.allclose(np.asarray(out[:, :, 0]),
                           np.asarray(out2[:, :, 0]))


# ---------------------------------------------------------------------------
# select_pack: fused compensate + rank + pack (topk_reduce's hot path)
# ---------------------------------------------------------------------------


def _select_pack_case(p, cap, seed, live_frac=0.8):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 4 * cap, size=(p, cap)).astype(np.int32)
    dead = rng.random(size=(p, cap)) > live_frac
    ids = np.where(dead, -1, ids)
    send = np.where(ids >= 0, rng.normal(size=(p, cap)), 0.0).astype(
        np.float32)
    carry = np.where(ids >= 0, rng.normal(size=(p, cap)), 0.0).astype(
        np.float32)
    return jnp.asarray(send), jnp.asarray(ids), jnp.asarray(carry)


@pytest.mark.parametrize("p,cap,k", [
    (1, 8, 2), (4, 64, 16), (3, 33, 7), (8, 128, 128),   # k == cap: frac=1.0
    (2, 16, 1), (5, 40, 39),
])
def test_select_pack_bit_exact_sweep(p, cap, k):
    """The kernel's selection set AND output order must match the XLA
    chain bit-for-bit: ranking reproduces jax.lax.top_k's total order
    (descending |value|, ties by position) and packing is a one-hot
    matmul with exactly one live term, so no float op reassociates."""
    send, ids, carry = _select_pack_case(p, cap, seed=p * 1000 + cap + k)
    want = ref.select_pack_ref(send, ids, carry, k=k)
    got = ops.select_pack(send, ids, carry, k=k, impl="pallas_interpret")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_select_pack_edge_rows():
    """Empty rows, all-dead rows, and rows with fewer live slots than k:
    dead picks carry id -1 and value 0, exactly like the chain."""
    p, cap, k = 4, 16, 8
    send, ids, carry = _select_pack_case(p, cap, seed=0)
    ids = ids.at[1].set(-1)                       # row 1 fully dead
    ids = ids.at[2, 3:].set(-1)                   # row 2: 3 live < k
    send = jnp.where(ids >= 0, send, 0.0)
    carry = jnp.where(ids >= 0, carry, 0.0)
    want = ref.select_pack_ref(send, ids, carry, k=k)
    got = ops.select_pack(send, ids, carry, k=k, impl="pallas_interpret")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    vals_k, ids_k, resid = got
    assert np.all(np.asarray(ids_k[1]) == -1)
    assert np.all(np.asarray(vals_k[1]) == 0.0)
    # a row with <= k live slots sends everything: residual all zero
    assert np.all(np.asarray(resid[2]) == 0.0)


def test_select_pack_duplicate_keys_tiebreak():
    """Equal |values| must break ties by position (top_k's order) — the
    case that catches a ranking comparator that is not a total order."""
    p, cap, k = 1, 12, 4
    ids = jnp.arange(12, dtype=jnp.int32).reshape(p, cap)
    send = jnp.full((p, cap), 0.5, jnp.float32)
    send = send.at[0, 7].set(-0.5)                # same |.|, negative
    carry = jnp.zeros((p, cap), jnp.float32)
    want = ref.select_pack_ref(send, ids, carry, k=k)
    got = ops.select_pack(send, ids, carry, k=k, impl="pallas_interpret")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_select_pack_capacity_fallback(impl):
    """Above MAX_CAPACITY the XLA chain still answers, while a Pallas impl
    raises like the raw kernel: the dispatcher never swaps in a path the
    caller did not ask for."""
    from repro.kernels import select_pack as sp

    p, cap, k = 2, sp.MAX_CAPACITY + 8, 4
    send, ids, carry = _select_pack_case(p, cap, seed=3)
    if impl == "xla":
        want = ref.select_pack_ref(send, ids, carry, k=k)
        got = ops.select_pack(send, ids, carry, k=k, impl=impl)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    else:
        with pytest.raises(ValueError, match="MAX_CAPACITY"):
            ops.select_pack(send, ids, carry, k=k, impl=impl)
    with pytest.raises(ValueError, match="MAX_CAPACITY"):
        sp.select_pack(send, ids, carry, k=k, interpret=True)


# ---------------------------------------------------------------------------
# owner_accumulate: the reverse-shuffle scatter-add behind the seam
# ---------------------------------------------------------------------------


def _routing_case(p, cap, f, seed, integer_grads=False):
    rng = np.random.default_rng(seed)
    ids = rng.integers(-1, f, size=(p, cap)).astype(np.int32)
    if integer_grads:
        g = rng.integers(-8, 9, size=(p, cap)).astype(np.float32)
    else:
        g = rng.normal(size=(p, cap)).astype(np.float32)
    g = np.where(ids >= 0, g, 0.0).astype(np.float32)
    return jnp.asarray(ids), jnp.asarray(g)


@pytest.mark.parametrize("p,cap,f,base", [
    (4, 16, 64, 0), (8, 32, 64, 16), (1, 64, 256, 0), (3, 10, 32, 8),
])
def test_owner_accumulate_integer_bit_exact(p, cap, f, base):
    """Integer-valued grads: every per-feature total is exactly
    representable, so reassociating the in-run addition order (matmul
    run totals vs scatter order) cannot change a bit — the kernel path
    must equal the XLA scatter-add exactly. This also proves the SET of
    addends per feature is identical."""
    ids, g = _routing_case(p, cap, f, seed=p + cap, integer_grads=True)
    acc = jnp.zeros((f,), jnp.float32)
    r0 = ops.owner_accumulate(ids, g, acc, base, impl="xla")
    r1 = ops.owner_accumulate(ids, g, acc, base, impl="pallas_interpret",
                              block=16)
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))


def test_owner_accumulate_float_tolerance():
    """General f32: in-run addition order differs between the two paths
    (documented at ops.owner_accumulate), so the contract is allclose at
    LSB-level tolerance, not bit equality."""
    ids, g = _routing_case(8, 64, 128, seed=7)
    acc = jnp.zeros((128,), jnp.float32)
    r0 = ops.owner_accumulate(ids, g, acc, 0, impl="xla")
    r1 = ops.owner_accumulate(ids, g, acc, 0, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(r0), np.asarray(r1),
                               rtol=1e-6, atol=1e-6)


def test_owner_accumulate_edge_shapes():
    """All-padding input is a no-op; all-one-feature input concentrates
    every add into one accumulator slot (the run spans many blocks)."""
    f = 32
    acc0 = jnp.arange(f, dtype=jnp.float32)       # non-zero start
    all_pad = jnp.full((4, 16), -1, jnp.int32)
    g = jnp.zeros((4, 16), jnp.float32)
    out = ops.owner_accumulate(all_pad, g, acc0, 0,
                               impl="pallas_interpret", block=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(acc0))
    one_id = jnp.full((4, 16), 5, jnp.int32)
    ones = jnp.ones((4, 16), jnp.float32)
    out = ops.owner_accumulate(one_id, ones, jnp.zeros((f,)), 0,
                               impl="pallas_interpret", block=8)
    want = np.zeros((f,), np.float32)
    want[5] = 64.0
    np.testing.assert_array_equal(np.asarray(out), want)


def test_owner_accumulate_base_offset_drop():
    """Features above this owner's [base, base+block) window and padding
    are dropped by mode="drop" on both paths. (Below-base ids cannot
    occur: route_build routes each id to its owner by id // block, so a
    received buffer only ever holds in-window ids and padding.)"""
    ids = jnp.asarray([[17, 18, 31, -1, 40]], jnp.int32)
    g = jnp.asarray([[2.0, 3.0, 4.0, 9.0, 5.0]], jnp.float32)
    acc = jnp.zeros((16,), jnp.float32)           # owner block [16, 32)
    r0 = ops.owner_accumulate(ids, g, acc, 16, impl="xla")
    r1 = ops.owner_accumulate(ids, g, acc, 16, impl="pallas_interpret",
                              block=4)
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    want = np.zeros((16,), np.float32)
    want[1], want[2], want[15] = 2.0, 3.0, 4.0
    np.testing.assert_array_equal(np.asarray(r0), want)


def test_owner_accumulate_routing_path_parity():
    """Against the REAL routing layout: route_build's request buffer ids
    (ascending unique per row, -1 tail) through both impls — the shape
    the strategies actually feed the seam."""
    from repro.core import sparse

    p, block, cap, f = 4, 16, 12, 64
    rng = np.random.default_rng(11)
    flat = jnp.asarray(rng.integers(-1, f, size=(48,)).astype(np.int32))
    routing = sparse.route_build(flat, p, block, cap)
    g = jnp.where(routing.req_ids >= 0,
                  jnp.asarray(rng.integers(-4, 5,
                                           size=(p, cap)).astype(np.float32)),
                  0.0)
    for base in (0, 16):
        r0 = ops.owner_accumulate(routing.req_ids, g,
                                  jnp.zeros((block,)), base, impl="xla")
        r1 = ops.owner_accumulate(routing.req_ids, g,
                                  jnp.zeros((block,)), base,
                                  impl="pallas_interpret", block=8)
        np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))


# ---------------------------------------------------------------------------
# the seam end to end: StepFns parity and strategy-contract conformance
# ---------------------------------------------------------------------------


def test_step_fns_parity_single_device():
    """topk_reduce train steps on a 1-device mesh: kernel_impl
    "pallas_interpret" (select_pack + owner_accumulate kernels live) is
    bit-identical to "xla" — params AND the error-feedback carry."""
    from repro.configs.base import DPMRConfig
    from repro.core import dpmr
    from repro.launch.mesh import make_host_mesh

    cfg = DPMRConfig(num_features=1 << 10, max_features_per_sample=8,
                     distribution="topk_reduce", topk_frac=0.25)
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(0)
    b = 32
    ids = rng.integers(-1, cfg.num_features, size=(b, 8)).astype(np.int32)
    vals = np.where(ids >= 0, rng.normal(size=(b, 8)), 0.0).astype(
        np.float32)
    batch = {"ids": jnp.asarray(ids), "vals": jnp.asarray(vals),
             "labels": jnp.asarray(
                 rng.integers(0, 2, size=(b,)).astype(np.int32))}
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        with jax.set_mesh(mesh):
            fns = dpmr.make_step_fns(cfg, mesh, b, kernel_impl=impl)
            st = dpmr.init_state(cfg, mesh)
            for _ in range(3):
                st, _ = fns.train_step(st, batch)
        outs[impl] = (np.asarray(st.cold), np.asarray(st.strat))
    for a, b_ in zip(outs["xla"], outs["pallas_interpret"]):
        np.testing.assert_array_equal(a, b_)


@pytest.mark.slow
def test_step_fns_parity_multidevice():
    """The same parity on a real 4-shard exchange (subprocess, emulated
    devices): the kernels sit between unchanged collectives, so every
    strategy that routes through the seam stays bit-identical."""
    import json
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parents[1]
    body = """
import json
import numpy as np
import jax
import jax.numpy as jnp
from repro.configs.base import DPMRConfig
from repro.core import dpmr
from repro.launch.mesh import make_host_mesh

out = {}
for dist in ("a2a", "topk_reduce"):
    cfg = DPMRConfig(num_features=1 << 10, max_features_per_sample=8,
                     distribution=dist, topk_frac=0.25)
    mesh = make_host_mesh(4, 1)
    rng = np.random.default_rng(0)
    b = 64
    ids = rng.integers(-1, cfg.num_features, size=(b, 8)).astype(np.int32)
    vals = np.where(ids >= 0, rng.normal(size=(b, 8)), 0.0).astype(
        np.float32)
    batch = {"ids": jnp.asarray(ids), "vals": jnp.asarray(vals),
             "labels": jnp.asarray(
                 rng.integers(0, 2, size=(b,)).astype(np.int32))}
    res = {}
    for impl in ("xla", "pallas_interpret"):
        with jax.set_mesh(mesh):
            fns = dpmr.make_step_fns(cfg, mesh, b, kernel_impl=impl)
            st = dpmr.init_state(cfg, mesh)
            for _ in range(3):
                st, _ = fns.train_step(st, batch)
        res[impl] = (np.asarray(st.cold), np.asarray(st.strat))
    out[dist] = {
        "cold_equal": bool(np.array_equal(res["xla"][0],
                                          res["pallas_interpret"][0])),
        "carry_equal": bool(np.array_equal(res["xla"][1],
                                           res["pallas_interpret"][1])),
        "cold_moved": bool(np.abs(res["xla"][0]).max() > 0),
    }
print(json.dumps(out))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for dist, r in out.items():
        assert r["cold_equal"] and r["carry_equal"], (dist, r)
        assert r["cold_moved"], (dist, r)


def test_pallas_impl_keeps_audit_green():
    """The strategy contract audit on kernel_impl="pallas" contexts: the
    kernels change lowering, never the collectives, so every analytic
    rule (W-MATCH, E-WIRE's declared-vs-traced wire, carry lifecycle)
    must stay green with the pallas path selected."""
    from repro.analysis import audit_registry, build_contexts

    contexts = tuple(
        actx._replace(ctx=actx.ctx._replace(kernel_impl="pallas"))
        for actx in build_contexts())
    report = audit_registry(contexts=contexts, engine_checks=False)
    assert report["ok"], [
        f for s in report["strategies"].values()
        for geo in s.values() if isinstance(geo, dict)
        for f in geo.get("findings", [])]
