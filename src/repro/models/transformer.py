"""Decoder-only transformer (dense family; chameleon reuses it with qk_norm).

Layers are stacked on a leading `layers` dim and executed with lax.scan +
optional remat — compile time and HLO size are independent of depth, which is
what makes the 126-layer llama3-405b dry-run tractable.

Sharding: parameters carry logical axes (see repro.sharding); activations get
with_sharding_constraint at block boundaries. The FSDP (`data`-axis) param
sharding *is* the DPMR dense face: XLA materializes per-layer all-gather
(distributeParameters) inside the scan and reduce-scatter of grads
(the feature-keyed reduce) in the backward pass.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import common, layers
from repro.sharding import Annotated

PREFILL_EXTRA = 32   # decode headroom appended to non-SWA prefill caches


def transformer_defs(cfg: ModelConfig) -> dict:
    from repro.models import moe as moe_mod

    layer = {
        "attn": layers.attn_defs(cfg),
        "mlp": moe_mod.moe_defs(cfg) if cfg.num_experts else layers.mlp_defs(cfg),
        "ln1": Annotated((cfg.d_model,), cfg.param_dtype, (None,)),
        "ln2": Annotated((cfg.d_model,), cfg.param_dtype, (None,)),
    }
    return {
        "layers": common.stack_defs(layer, cfg.num_layers),
        **common.embed_defs(cfg),
    }


def _ffn(p, x, cfg: ModelConfig, moe_group: int = 512):
    """Dense MLP or MoE; returns (out, aux_loss)."""
    if cfg.num_experts:
        from repro.models import moe as moe_mod

        return moe_mod.moe_block(p, x, cfg, group_size=moe_group)
    return layers.mlp_block(p, x, cfg), jnp.float32(0.0)


def _constrain(x, spec_tail):
    """Shard batch over DP axes + given tail; no-op outside a mesh context."""
    try:
        import jax.interpreters.pxla  # noqa: F401

        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return x
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        lead = dp if len(dp) > 1 else (dp[0] if dp else None)
        tail = [
            t if (t is None or t in mesh.axis_names) else None for t in spec_tail
        ]
        # drop axis if it does not divide
        for i, t in enumerate(tail):
            if t is not None and x.shape[1 + i] % mesh.shape[t] != 0:
                tail[i] = None
        if lead is not None and isinstance(lead, tuple):
            sz = 1
            for a in lead:
                sz *= mesh.shape[a]
            if x.shape[0] % sz != 0:
                lead = None
        elif lead is not None and x.shape[0] % mesh.shape[lead] != 0:
            lead = None
        return jax.lax.with_sharding_constraint(x, P(lead, *tail))
    except Exception:
        return x


def decoder_layer(p, x, cfg: ModelConfig, positions, sp: bool = True,
                  attn_mode: str = "auto", moe_group: int = 512):
    """x: (B, S, D) -> ((B, S, D), aux). Pre-norm residual block.

    sp: sequence-parallel residual — the stream (and thus remat-saved
    activations) is sharded over `model` along S between blocks; attention/
    MLP internals re-shard to head/ff parallelism as GSPMD propagates from
    the weight shardings (Megatron-SP on the cheap).
    attn_mode="cp": attention computed context-parallel (kv-only gather)."""
    tail = ("model", None) if sp else (None, None)
    h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
    attn = layers.attention_block(p["attn"], h, cfg, positions,
                                  attn_mode=attn_mode)
    x = x + _constrain(attn, tail)
    h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    if attn_mode == "cp" and sp and not cfg.num_experts:
        # hybrid: attention is context-parallel (kv-only gather), but the
        # dense MLP goes Megatron-SP — gather h over S once, compute with
        # the ff dim sharded, reduce-scatter back via the residual
        # constraint. Leaving h S-sharded makes GSPMD all-gather the FULL
        # mlp weights per layer instead (36 GiB/layer on llama3-405b).
        # MoE layers skip this: routing/dispatch are per-token ops, so the
        # S-sharded stream feeds the expert a2a directly.
        h = _constrain(h, (None, None))
    ff, aux = _ffn(p["mlp"], h, cfg, moe_group)
    x = x + _constrain(ff, tail)
    return x, aux


def forward(params, tokens, cfg: ModelConfig,
            parallel: ParallelConfig | None = None):
    """Train/prefill forward -> (logits (B, S, V) f32, aux_loss scalar)."""
    parallel = parallel or ParallelConfig()
    b, s = tokens.shape
    sp = parallel.seq_shard
    tail = ("model", None) if sp else (None, None)
    x = common.embed_tokens(params, tokens, cfg)
    x = _constrain(x, tail)
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)

    def body(carry, lp):
        x, aux = carry
        x, a = decoder_layer(lp, x, cfg, positions, sp=sp,
                             attn_mode=parallel.attn_mode,
                             moe_group=parallel.moe_group)
        return (x, aux + a), None

    if parallel.remat != "none":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
            if parallel.remat == "full"
            else jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )
    carry = (x, jnp.float32(0.0))
    if parallel.scan_layers:
        (x, aux), _ = jax.lax.scan(body, carry, params["layers"])
    else:
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            carry, _ = body(carry, lp)
        x, aux = carry

    x = layers.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return common.lm_head(params, x, cfg), aux


def prefill(params, tokens, cfg: ModelConfig,
            parallel: ParallelConfig | None = None):
    """Serve-side prefill: returns (last-token logits (B,1,V), cache).

    Collects per-layer K/V during the layer scan; under SWA the cache keeps
    the last `window` positions (ring-aligned because S % window == 0 for
    the assigned shapes).
    """
    parallel = parallel or ParallelConfig()
    b, s = tokens.shape
    x = common.embed_tokens(params, tokens, cfg)
    x = _constrain(x, (None, None))
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    slots = min(s, cfg.sliding_window) if cfg.sliding_window else s

    def body(x, lp):
        h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = layers.project_q(lp["attn"], h, cfg)
        k, v = layers.project_kv(lp["attn"], h, cfg)
        if cfg.rope_theta:
            sin, cos = layers.rope_tables(positions, cfg.resolved_head_dim,
                                          cfg.rope_theta)
            q = layers.apply_rope(q, sin, cos)
            k = layers.apply_rope(k, sin, cos)
        att = layers.blocked_causal_attention(q, k, v,
                                              window=cfg.sliding_window)
        x = x + layers.project_out(lp["attn"], att, x.dtype)
        h = layers.rms_norm(x, lp["ln2"], cfg.norm_eps)
        ff, _ = _ffn(lp["mlp"], h, cfg)
        x = x + ff
        return x, (k[:, -slots:], v[:, -slots:])

    if parallel.remat != "none":
        body = jax.checkpoint(body)
    x, (k_all, v_all) = common.scan_or_unroll(
        body, x, params["layers"], unroll=not parallel.scan_layers)
    x = layers.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = common.lm_head(params, x[:, -1:], cfg)
    if not cfg.sliding_window:
        # headroom for subsequent decode steps (SWA keeps the exact ring)
        pad = ((0, 0), (0, 0), (0, PREFILL_EXTRA), (0, 0), (0, 0))
        k_all = jnp.pad(k_all, pad)
        v_all = jnp.pad(v_all, pad)
    cache = {"k": k_all, "v": v_all,
             "length": jnp.full((b,), s, jnp.int32)}
    return logits, cache


# ---------------------------------------------------------------------------
# KV-cache serve path
# ---------------------------------------------------------------------------


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """KV cache defs (ring buffer of sliding_window slots under SWA).

    Sharding: kv_heads over the model axis when divisible (16-way production
    meshes); otherwise the SLOT dim shards over model (GQA head counts of
    1/4/8 would replicate a 1 TiB llama-405b decode_32k cache)."""
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    kh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    head_dim_ok = kh % 16 == 0
    logical = ("layers", "batch", None, "kv_heads", None) if head_dim_ok \
        else ("layers", "batch", "kv_seq", None, None)
    kv = Annotated((cfg.num_layers, batch, slots, kh, hd), cfg.dtype, logical)
    return {
        "k": kv,
        "v": Annotated(kv.shape, cfg.dtype, kv.logical),
        "length": Annotated((batch,), "int32", ("batch",)),
    }


def decode_step(params, cache, tokens, cfg: ModelConfig,
                unroll: bool = False):
    """One decode step. tokens: (B, 1) int32; cache per cache_defs.

    Returns (logits (B, 1, V) f32, new_cache).
    """
    b = tokens.shape[0]
    slots = cache["k"].shape[2]
    pos = cache["length"]                                  # (B,)
    x = common.embed_tokens(params, tokens, cfg)

    def body(x, per_layer):
        lp, k_l, v_l = per_layer
        h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = layers.project_q(lp["attn"], h, cfg)
        k_new, v_new = layers.project_kv(lp["attn"], h, cfg)
        if cfg.rope_theta:
            sin, cos = layers.rope_tables(
                pos[:, None], cfg.resolved_head_dim, cfg.rope_theta
            )
            q = layers.apply_rope(q, sin, cos)
            k_new = layers.apply_rope(k_new, sin, cos)
        if cfg.sliding_window:
            slot = pos % slots            # ring buffer over window slots
        else:
            slot = jnp.minimum(pos, slots - 1)
        # one-hot masked update instead of scatter: elementwise ops keep the
        # slot-sharded cache sharding intact (a scatter on a sharded dim
        # makes GSPMD reshard the whole cache)
        oh = jax.nn.one_hot(slot, slots, dtype=k_l.dtype)[:, :, None, None]
        k_l = k_l * (1 - oh) + k_new[:, 0][:, None] * oh
        v_l = v_l * (1 - oh) + v_new[:, 0][:, None] * oh
        att = layers.decode_attention(
            q, k_l, v_l, pos + 1, window=cfg.sliding_window
        )
        x = x + layers.project_out(lp["attn"], att, x.dtype)
        h = layers.rms_norm(x, lp["ln2"], cfg.norm_eps)
        ff, _ = _ffn(lp["mlp"], h, cfg)
        x = x + ff
        return x, (k_l, v_l)

    x, (k_all, v_all) = common.scan_or_unroll(
        body, x, (params["layers"], cache["k"], cache["v"]), unroll=unroll
    )
    x = layers.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = common.lm_head(params, x, cfg)
    new_cache = {"k": k_all, "v": v_all, "length": cache["length"] + 1}
    return logits, new_cache
