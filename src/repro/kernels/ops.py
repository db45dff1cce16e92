"""Jitted dispatch wrappers for the Pallas kernels — the `kernel_impl`
seam between the DPMR hot path and its two lowerings.

`impl` selects the backend (`KERNEL_IMPLS`):
  - "xla"               pure-jnp reference chain lowered by XLA — the
                        DEFAULT and the fallback on CPU/GPU backends
                        ("jnp" is the legacy spelling, kept as an alias)
  - "pallas"            real TPU lowering (pl.pallas_call, interpret=False)
  - "pallas_interpret"  kernel body executed in python on CPU — the
                        correctness/testing mode (bit-parity with "xla"
                        is asserted by tests/test_kernels.py)

Production call sites (see docs/KERNELS.md for the paper-algorithm map):
  - `sigmoid_grad`      computeGradients map body (core.dpmr step fns)
  - `select_pack`       topk_reduce's fused compensate+rank+pack
                        (api.strategies.TopKReduceStrategy.reduce)
  - `owner_accumulate`  the reverse-shuffle scatter-add, rebuilt as
                        sort + `segment_sum_sorted` run totals so owners
                        do ONE add per unique feature instead of one per
                        received slot (api.strategies reduce paths)
  - `flash_attention`   reference-grade only: retained for the dense-face
                        attention experiments, no sparse-path caller —
                        exercised by tests, not by any engine step

The knob threads end to end: `DPMRConfig.kernel_impl` (or the engine /
`make_step_fns` argument) -> `StrategyContext.kernel_impl` -> these
wrappers. Everything here is shape-polymorphic jax; no backend is probed
at import time.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import segment_sum as _ss
from repro.kernels import select_pack as _sp
from repro.kernels import sigmoid_grad as _sg

DEFAULT_IMPL = "xla"
KERNEL_IMPLS = ("xla", "jnp", "pallas", "pallas_interpret")


def normalize_impl(impl: str) -> str:
    """Canonical impl name: "xla" and "jnp" are the same (reference) path;
    unknown names raise instead of silently running the fallback."""
    if impl not in KERNEL_IMPLS:
        raise ValueError(
            f"unknown kernel_impl {impl!r}; expected one of {KERNEL_IMPLS}")
    return "xla" if impl == "jnp" else impl


def is_pallas(impl: str) -> bool:
    """True when `impl` routes to a Pallas kernel (real or interpreted)."""
    return normalize_impl(impl) in ("pallas", "pallas_interpret")


def sigmoid_grad(vals, theta, labels, *, impl: str = DEFAULT_IMPL,
                 block_b: int = 256):
    if not is_pallas(impl):
        return _ref.sigmoid_grad_ref(vals, theta, labels)
    return _sg.sigmoid_grad(vals, theta, labels, block_b=block_b,
                            interpret=(impl == "pallas_interpret"))


def segment_sum_sorted(ids, grads, *, impl: str = DEFAULT_IMPL,
                       block: int = 256):
    if not is_pallas(impl):
        return _ref.segment_sum_sorted_ref(ids, grads)
    return _ss.segment_sum_sorted(ids, grads, block=block,
                                  interpret=(impl == "pallas_interpret"))


def select_pack(send, ids, carry_slots, *, k: int, impl: str = DEFAULT_IMPL):
    """Fused top-k select+pack (see select_pack.py). A Pallas impl above
    the kernel's `MAX_CAPACITY` raises (the kernel refuses it) instead of
    quietly running the XLA chain the caller did not ask for."""
    if not is_pallas(impl):
        return _ref.select_pack_ref(send, ids, carry_slots, k=k)
    return _sp.select_pack(send, ids, carry_slots, k=k,
                           interpret=(impl == "pallas_interpret"))


def owner_accumulate(req_ids, grads, acc_local, base, *,
                     impl: str = DEFAULT_IMPL, block: int = 256):
    """The reverse-shuffle scatter-add, kernelized.

    XLA path: `core.sparse.owner_accumulate`'s scatter-add — one add per
    received (P, cap) slot, serialized scatter on TPU. Pallas path: sort
    the received slots by feature id (padding last — the same key trick as
    `route_build`), reduce each run to ONE total with the masked-matmul
    `segment_sum_sorted` combiner, and scatter-add run totals; the owner
    does one memory add per UNIQUE feature instead of one per slot.

    Semantics match the XLA path exactly for sums that are exactly
    representable (each feature's total is the same set of addends); for
    general f32 the in-run addition order differs (matmul reduction vs
    scatter order), a documented LSB-level tolerance —
    tests/test_kernels.py pins both.
    """
    if not is_pallas(impl):
        # late import: core.sparse is the routing layer above this one
        from repro.core import sparse
        return sparse.owner_accumulate(req_ids, grads, acc_local, base)
    ids = req_ids.reshape(-1)
    g = jnp.where(ids >= 0, grads.reshape(-1), 0.0)
    sort_key = jnp.where(ids >= 0, ids, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(sort_key, stable=True)
    key_s = sort_key[order]
    ids_s = jnp.where(key_s == jnp.iinfo(jnp.int32).max, -1, key_s)
    totals = _ss.segment_sum_sorted(
        ids_s, g[order], block=block,
        interpret=(impl == "pallas_interpret"))
    # run totals live at run ends, zeros elsewhere: scattering the whole
    # vector adds 0.0 at non-end slots (a no-op) and drops padding
    local = jnp.where(ids_s >= 0, ids_s - base, acc_local.shape[0])
    return acc_local.at[local].add(totals, mode="drop")


def flash_attention(q, k, v, *, causal: bool = True,
                    impl: str = DEFAULT_IMPL, block_q: int = 128,
                    block_k: int = 128):
    if not is_pallas(impl):
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k,
                               interpret=(impl == "pallas_interpret"))
