"""Mixture-of-Experts FFN with group-limited top-k dispatch.

The dispatch is the DPMR sparse face applied to experts: experts are
"features", tokens are "samples", the top-k routing table is the inverted
index, and the (token -> expert buffer) shuffle is distributeParameters in
reverse (samples travel to parameter shards). Expert-capacity padding plays
the role of the paper's sub-feature sharding: it bounds the per-owner buffer
exactly like splitting a hot feature's sample list bounds an HDFS line.

Group-limited dispatch: tokens are split into groups of `group_size`; within
a group the dispatch tensor is (g, E, C) with C = g * k * cf / E, so its size
is g*k*cf per token (linear, not quadratic, in total tokens).

Sharding: expert weights carry the `experts` logical axis -> `model` mesh
axis when divisible (phi3.5: 16 experts over 16-way TP = pure EP; the
(group->expert) reshard lowers to an all-to-all). When E does not divide the
axis (mixtral: 8 over 16), experts replicate and the `ff` dim shards instead
(TP-MoE) — same FLOPs, different collective mix; both appear in the roofline.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding import Annotated

GROUP_SIZE = 512


def _constrain_ep(x, e: int, spec_dims):
    """Expert-parallel sharding constraint (no-op outside a mesh or when E
    does not divide the model axis). spec_dims: tuple of axis names/None per
    dim. Forcing (group->data, expert->model) on the dispatch buffers makes
    GSPMD reshard with all-to-all-equivalent wire bytes instead of
    all-gathering the whole buffer (16x on phi3.5)."""
    import jax
    from jax.sharding import PartitionSpec as P

    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return x
        dims = []
        for i, ax in enumerate(spec_dims):
            if ax is None or ax not in mesh.axis_names or \
                    x.shape[i] % mesh.shape[ax] != 0:
                dims.append(None)
            else:
                dims.append(ax)
        return jax.lax.with_sharding_constraint(x, P(*dims))
    except Exception:
        return x


def moe_defs(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    pt = cfg.param_dtype
    return {
        "router": Annotated((d, e), pt, ("mlp_embed", None)),
        "wi_gate": Annotated((e, d, f), pt, ("experts", "mlp_embed", "ff")),
        "wi_up": Annotated((e, d, f), pt, ("experts", "mlp_embed", "ff")),
        "wo": Annotated((e, f, d), pt, ("experts", "ff", "mlp_embed")),
    }


def expert_capacity(cfg: ModelConfig, group_size: int) -> int:
    c = int(group_size * cfg.experts_per_token * cfg.capacity_factor
            / cfg.num_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def moe_block(p, x, cfg: ModelConfig,
              group_size: int = GROUP_SIZE) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    g = min(group_size, b * s)
    assert (b * s) % g == 0, (b, s, g)
    ng = b * s // g
    cap = expert_capacity(cfg, g)

    xg = x.reshape(ng, g, d)
    logits = jnp.einsum("ngd,de->nge", xg, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (ng, g, E) f32

    gate_vals, idx = jax.lax.top_k(probs, k)                   # (ng, g, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, slot) within its expert buffer
    sel = jax.nn.one_hot(idx, e, dtype=jnp.int32)              # (ng, g, k, E)
    flat = sel.reshape(ng, g * k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                          # (ng, g*k, E)
    keep = (pos < cap) & (flat > 0)
    # dispatch/combine tensors (ng, g*k, E, C)
    disp = jax.nn.one_hot(pos, cap, dtype=x.dtype) * keep[..., None].astype(x.dtype)
    gate_flat = gate_vals.reshape(ng, g * k)
    comb = disp * gate_flat[..., None, None].astype(x.dtype)
    # fold k back onto tokens: (ng, g, k, E, C) -> sum k -> (ng, g, E, C)
    disp = disp.reshape(ng, g, k, e, cap).sum(axis=2)
    comb = comb.reshape(ng, g, k, e, cap).sum(axis=2)

    # tokens -> expert buffers (the DPMR shuffle; resharding group->expert
    # ownership lowers to all-to-all under EP)
    xin = jnp.einsum("ngec,ngd->necd", disp, xg,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    xin = _constrain_ep(xin, e, ("data", "model", None, None))
    hg = jnp.einsum("necd,edf->necf", xin, p["wi_gate"].astype(x.dtype),
                    preferred_element_type=jnp.float32)
    hu = jnp.einsum("necd,edf->necf", xin, p["wi_up"].astype(x.dtype),
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(hg) * hu).astype(x.dtype)
    yo = jnp.einsum("necf,efd->necd", h, p["wo"].astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    yo = _constrain_ep(yo, e, ("data", "model", None, None))
    out = jnp.einsum("ngec,necd->ngd", comb, yo,
                     preferred_element_type=jnp.float32).astype(x.dtype)

    # Switch-style load-balancing auxiliary loss
    density = jnp.mean(flat.astype(jnp.float32), axis=1)       # (ng, E)
    density_prob = jnp.mean(probs, axis=1)                     # (ng, E)
    aux = jnp.mean(jnp.sum(density * density_prob, axis=-1)) * (e * e / k)

    return out.reshape(b, s, d), aux
