"""Shared model layers: norms, RoPE, blocked attention (GQA/SWA), MLPs.

All layers are pure functions over parameter pytrees. Parameter *definitions*
(shape/dtype/logical axes) are built by the ``*_defs`` functions; the logical
axes drive sharding (see repro.sharding). Attention is implemented blockwise
(online softmax) so 32k-context prefill never materializes an S x S score
matrix; a triangular python-unrolled schedule avoids causal-mask FLOP waste
for moderate block counts (the Pallas kernel in repro.kernels.flash_attention
is the TPU-optimized equivalent and is validated against this code).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.sharding import Annotated

# Dry-run cost-probe mode: XLA's cost_analysis counts while-loop bodies once,
# so probes (benchmarks/roofline.py via launch/dryrun.py --probe) set this to
# eliminate inner scans: python-unrolled q loops + single kv blocks. Never
# enabled for execution — compile-only probes (ShapeDtypeStructs).
PROBE_UNROLL = False

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# numerics helpers
# ---------------------------------------------------------------------------


def adtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def head_norm(x, scale, eps: float):
    """qk-norm: RMS-normalize the head_dim axis (chameleon)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------


def rope_tables(positions, head_dim: int, theta: float):
    """positions: (...,) int32 -> (sin, cos) of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, sin, cos):
    """x: (B, S, H, D); sin/cos: (B, S, D//2) or (S, D//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    else:
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def sinusoidal_positions(length: int, d_model: int, dtype=jnp.float32):
    pos = np.arange(length)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d_model)
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, dtype=dtype)


# ---------------------------------------------------------------------------
# attention parameter defs
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig) -> dict:
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pt = cfg.param_dtype
    defs = {
        "wq": Annotated((d, h, hd), pt, ("embed", "heads", None)),
        "wk": Annotated((d, kh, hd), pt, ("embed", "kv_heads", None)),
        "wv": Annotated((d, kh, hd), pt, ("embed", "kv_heads", None)),
        "wo": Annotated((h, hd, d), pt, ("heads", None, "embed")),
    }
    if cfg.qk_norm:
        defs["q_norm"] = Annotated((hd,), pt, (None,))
        defs["k_norm"] = Annotated((hd,), pt, (None,))
    return defs


def mlp_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pt = cfg.param_dtype
    if cfg.mlp_type == "swiglu":
        return {
            "wi_gate": Annotated((d, f), pt, ("mlp_embed", "ff")),
            "wi_up": Annotated((d, f), pt, ("mlp_embed", "ff")),
            "wo": Annotated((f, d), pt, ("ff", "mlp_embed")),
        }
    return {
        "wi": Annotated((d, f), pt, ("mlp_embed", "ff")),
        "wo": Annotated((f, d), pt, ("ff", "mlp_embed")),
    }


# ---------------------------------------------------------------------------
# blocked attention core
# ---------------------------------------------------------------------------


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, kh, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, hd)).reshape(
        b, s, kh * n_rep, hd
    )


def _attn_block(q, k, v, m, l, acc, mask, scale):
    """One online-softmax step. q:(B,qb,H,D) k/v:(B,kb,H,D) mask:(qb,kb)|None."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def blocked_causal_attention(
    q, k, v, *, window: int = 0, q_block: int = 1024, kv_block: int = 1024,
    unroll_limit: int = 64,
):
    """Causal (optionally sliding-window) attention, O(S*block) memory.

    q: (B, Sq, H, hd); k, v: (B, Skv, KH, hd) with H % KH == 0. Sq == Skv
    (training / prefill; use `decode_attention` for cached decode).

    Schedule: python-unrolled triangular q-blocks (no masked-FLOP waste) when
    the block count is <= unroll_limit, else a scan with per-block masking.
    Sliding window uses a left-pad + static slice so per-q-block work is
    uniform and independent of position.
    """
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    scale = 1.0 / math.sqrt(hd)
    q_block = min(q_block, sq)
    n_q = sq // q_block if sq % q_block == 0 else 1
    if sq % q_block != 0:
        q_block = sq
        n_q = 1

    if window:
        return _swa_attention(q, k, v, window, q_block, kv_block, scale)
    if n_q <= unroll_limit:
        return _triangular_attention(q, k, v, q_block, kv_block, scale)
    return _masked_scan_attention(q, k, v, q_block, kv_block, scale)


def _finalize(acc, l):
    return (acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None])


def _triangular_attention(q, k, v, q_block, kv_block, scale):
    """Python-unrolled q blocks; q block i sees kv[0 : (i+1)*q_block]."""
    b, sq, h, hd = q.shape
    outs = []
    for i in range(sq // q_block):
        qs = i * q_block
        qi = q[:, qs : qs + q_block]
        extent = qs + q_block                       # static
        ki, vi = k[:, :extent], v[:, :extent]
        m = jnp.full((b, h, q_block), -jnp.inf, jnp.float32)
        l = jnp.zeros((b, h, q_block), jnp.float32)
        acc = jnp.zeros((b, q_block, h, hd), jnp.float32)
        kb = extent if PROBE_UNROLL else min(kv_block, extent)
        n_kv = extent // kb
        rem = extent - n_kv * kb

        def body(carry, blk):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(ki, blk * kb, kb, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vi, blk * kb, kb, axis=1)
            # causal mask only matters for the diagonal region
            qpos = qs + jnp.arange(q_block)
            kpos = blk * kb + jnp.arange(kb)
            mask = qpos[:, None] >= kpos[None, :]
            return _attn_block(qi, ks, vs, m, l, acc, mask, scale), None

        if n_kv:
            (m, l, acc), _ = jax.lax.scan(body, (m, l, acc), jnp.arange(n_kv))
        if rem:
            ks, vs = ki[:, n_kv * kb :], vi[:, n_kv * kb :]
            qpos = qs + jnp.arange(q_block)
            kpos = n_kv * kb + jnp.arange(rem)
            mask = qpos[:, None] >= kpos[None, :]
            m, l, acc = _attn_block(qi, ks, vs, m, l, acc, mask, scale)
        outs.append(_finalize(acc, l))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _masked_scan_attention(q, k, v, q_block, kv_block, scale):
    """Scan over q blocks x kv blocks with causal masking (tolerates waste)."""
    b, sq, h, hd = q.shape
    kv_block = min(kv_block, sq)
    n_q, n_kv = sq // q_block, sq // kv_block

    def q_body(_, iq):
        qi = jax.lax.dynamic_slice_in_dim(q, iq * q_block, q_block, axis=1)
        m = jnp.full((b, h, q_block), -jnp.inf, jnp.float32)
        l = jnp.zeros((b, h, q_block), jnp.float32)
        acc = jnp.zeros((b, q_block, h, hd), jnp.float32)

        def kv_body(carry, ik):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(k, ik * kv_block, kv_block, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, ik * kv_block, kv_block, axis=1)
            qpos = iq * q_block + jnp.arange(q_block)
            kpos = ik * kv_block + jnp.arange(kv_block)
            mask = qpos[:, None] >= kpos[None, :]
            return _attn_block(qi, ks, vs, m, l, acc, mask, scale), None

        (m, l, acc), _ = jax.lax.scan(kv_body, (m, l, acc), jnp.arange(n_kv))
        return None, _finalize(acc, l)

    _, outs = jax.lax.scan(q_body, None, jnp.arange(n_q))
    # outs: (n_q, B, q_block, H, hd) -> (B, S, H, hd)
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd).astype(q.dtype)


def _swa_attention(q, k, v, window, q_block, kv_block, scale):
    """Sliding-window causal attention via left-pad + static slices.

    For q block starting at qs, the visible kv range is
    (qs - window, qs + q_block]; after left-padding k/v by `window`, that is
    the STATIC-size slice padded[qs : qs + window + q_block].
    """
    b, sq, h, hd = q.shape
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    span = window + q_block
    n_q = sq // q_block
    if PROBE_UNROLL:
        kv_block = span

    def q_body(_, iq):
        qs = iq * q_block
        qi = jax.lax.dynamic_slice_in_dim(q, qs, q_block, axis=1)
        ki = jax.lax.dynamic_slice_in_dim(kp, qs, span, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(vp, qs, span, axis=1)
        m = jnp.full((b, h, q_block), -jnp.inf, jnp.float32)
        l = jnp.zeros((b, h, q_block), jnp.float32)
        acc = jnp.zeros((b, q_block, h, hd), jnp.float32)
        kb = min(kv_block, span)
        n_kv = span // kb
        rem = span - n_kv * kb

        def kv_body(carry, ik):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(ki, ik * kb, kb, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vi, ik * kb, kb, axis=1)
            # global positions: q = qs + i ; k = qs - window + ik*kb + j
            qpos = jnp.arange(q_block)[:, None] + window          # relative
            kpos = ik * kb + jnp.arange(kb)[None, :]
            valid = (kpos <= qpos) & (kpos > qpos - window)
            # also mask the left padding (global k index >= 0)
            valid &= (qs - window + kpos) >= 0
            return _attn_block(qi, ks, vs, m, l, acc, valid, scale), None

        (m, l, acc), _ = jax.lax.scan(kv_body, (m, l, acc), jnp.arange(n_kv))
        if rem:
            ks, vs = ki[:, n_kv * kb :], vi[:, n_kv * kb :]
            qpos = jnp.arange(q_block)[:, None] + window
            kpos = n_kv * kb + jnp.arange(rem)[None, :]
            valid = (kpos <= qpos) & (kpos > qpos - window)
            valid &= (qs - window + kpos) >= 0
            m, l, acc = _attn_block(qi, ks, vs, m, l, acc, valid, scale)
        return None, _finalize(acc, l)

    if PROBE_UNROLL:
        outs = [q_body(None, jnp.int32(i))[1] for i in range(n_q)]
        outs = jnp.stack(outs, 0)
    else:
        _, outs = jax.lax.scan(q_body, None, jnp.arange(n_q))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd).astype(q.dtype)


def context_parallel_attention(q, k, v, *, causal: bool = True,
                               window: int = 0, axis: str = "model",
                               kv_block: int = 1024):
    """Context-parallel attention: q (and the output) stay SEQUENCE-sharded
    over `axis`; only k/v are gathered (GQA: KH heads ~ D/16 of the residual
    bytes). This replaces the Megatron-SP all-gather(x)+reduce-scatter(out)
    pair around attention — the dominant collective in the train-cell
    baselines — and also un-replicates attention for archs whose head count
    does not divide the model axis (whisper: 12 heads vs 16).

    Formulation: q is reshaped to (B, C, S/C, H, hd) with the CHUNK dim C
    equal to (and sharded over) the model-axis size; k/v are constrained
    replicated (GSPMD inserts exactly one kv all-gather). The kv dimension
    is processed with an online-softmax scan, so no S x S buffer exists and
    no sharded dim is ever dynamically sliced (plain pjit — no shard_map;
    masking handles causality, ~2x masked-FLOP waste on attention).

    Falls back to the blocked implementations when there is no model axis
    or S does not divide it.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    b, s, h, hd = q.shape
    if (mesh is None or mesh.empty or axis not in mesh.axis_names
            or mesh.shape[axis] == 1 or s % mesh.shape[axis] != 0):
        if not causal:
            return _bidirectional_blocked(q, k, v)
        return blocked_causal_attention(q, k, v, window=window)

    c = int(mesh.shape[axis])
    s_loc = s // c
    kh = k.shape[2]
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    scale = 1.0 / math.sqrt(hd)
    skv = k.shape[1]
    kb = skv if (PROBE_UNROLL or skv % kv_block) else kv_block
    n_kv = skv // kb

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    lead = dp if len(dp) > 1 else (dp[0] if dp else None)
    if lead is not None:
        sz = mesh.shape[dp[0]] if len(dp) == 1 else \
            int(np.prod([mesh.shape[a] for a in dp]))
        if b % sz != 0:
            lead = None
    qc = q.reshape(b, c, s_loc, h, hd)
    qc = jax.lax.with_sharding_constraint(qc, P(lead, axis, None, None, None))
    k = jax.lax.with_sharding_constraint(k, P(lead, None, None, None))
    v = jax.lax.with_sharding_constraint(v, P(lead, None, None, None))

    # global q positions per (chunk, local) element
    qpos = (jnp.arange(c)[:, None] * s_loc
            + jnp.arange(s_loc)[None, :])                    # (C, S_loc)

    def kv_body(carry, ik):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, ik * kb, kb, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, ik * kb, kb, axis=1)
        sblk = jnp.einsum("bcqhd,bkhd->bchqk", qc.astype(jnp.float32),
                          ks.astype(jnp.float32)) * scale
        kpos = ik * kb + jnp.arange(kb)                      # (kb,)
        if causal and window:
            mask = (qpos[:, :, None] >= kpos[None, None, :]) & \
                (kpos[None, None, :] > qpos[:, :, None] - window)
        elif causal:
            mask = qpos[:, :, None] >= kpos[None, None, :]
        else:
            mask = jnp.ones((c, s_loc, kb), bool)
        sblk = jnp.where(mask[None, :, None, :, :], sblk, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sblk, axis=-1))
        p = jnp.exp(sblk - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bchqk,bkhd->bchqd", p.astype(v.dtype), vs,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, c, h, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, c, h, s_loc), jnp.float32)
    a0 = jnp.zeros((b, c, h, s_loc, hd), jnp.float32)
    if PROBE_UNROLL:
        carry = (m0, l0, a0)
        for i in range(n_kv):
            carry, _ = kv_body(carry, jnp.int32(i))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                      jnp.arange(n_kv))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 2, 3)                # (B, C, S_loc, H, hd)
    out = jax.lax.with_sharding_constraint(
        out, P(lead, axis, None, None, None))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def full_attention(q, k, v, *, causal: bool = True):
    """Reference O(S^2)-memory attention (small shapes / oracles only)."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd)
    if causal:
        skv = k.shape[1]
        mask = jnp.arange(sq)[:, None] + (skv - sq) >= jnp.arange(skv)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _constrain_scores(scores):
    """Keep decode scores sharded over the cache-slot dim (last axis): the
    softmax over a sharded axis costs two tiny all-reduces, vs GSPMD's
    default of all-gathering the slot-sharded KV cache per layer (~1 GiB per
    layer on yi-6b decode_32k)."""
    try:
        from jax.sharding import PartitionSpec as P

        mesh = jax.sharding.get_abstract_mesh()
        if (mesh is None or mesh.empty or "model" not in mesh.axis_names
                or scores.shape[-1] % mesh.shape["model"] != 0):
            return scores
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        lead = dp if len(dp) > 1 else (dp[0] if dp else None)
        if lead is not None:
            sz = int(np.prod([mesh.shape[a] for a in
                              (dp if isinstance(lead, tuple) else (lead,))]))
            if scores.shape[0] % sz != 0:
                lead = None
        return jax.lax.with_sharding_constraint(
            scores, P(lead, None, None, "model"))
    except Exception:
        return scores


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0):
    """Single-step decode: q (B,1,H,hd) vs cache (B,S,KH,hd), masked to
    cache_len (int32 scalar or (B,) vector). Window: ring-buffer semantics —
    every cache slot is valid (caller maintains the ring)."""
    b, s, kh, hd = k_cache.shape
    h = q.shape[2]
    k = _repeat_kv(k_cache, h // kh)
    v = _repeat_kv(v_cache, h // kh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    scores = _constrain_scores(scores)
    if window:
        valid = jnp.arange(s)[None, :] < jnp.reshape(
            jnp.minimum(cache_len, s), (-1, 1)
        )
    else:
        valid = jnp.arange(s)[None, :] < jnp.reshape(cache_len, (-1, 1))
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention module (projections + core + output)
# ---------------------------------------------------------------------------


def project_q(p, x, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.qk_norm:
        q = head_norm(q, p["q_norm"], cfg.norm_eps)
    return q


def project_kv(p, x, cfg: ModelConfig):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.qk_norm:
        k = head_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v


def project_out(p, attn_out, x_dtype):
    return jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"].astype(x_dtype),
                      preferred_element_type=jnp.float32).astype(x_dtype)


def attention_block(p, x, cfg: ModelConfig, positions, *, causal=True,
                    kv_x=None, use_blocked=True, attn_mode: str = "auto"):
    """Full attention block for train/prefill. kv_x: cross-attention source.

    attn_mode="cp": context-parallel — q/output sequence-sharded over the
    model axis, kv-only gather (see context_parallel_attention)."""
    src = x if kv_x is None else kv_x
    q = project_q(p, x, cfg)
    k, v = project_kv(p, src, cfg)
    if cfg.rope_theta:
        sin, cos = rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        if kv_x is None:
            k = apply_rope(k, sin, cos)
    if attn_mode == "cp":
        out = context_parallel_attention(
            q, k, v, causal=(causal and kv_x is None),
            window=cfg.sliding_window)
    elif kv_x is not None or not causal:
        out = full_attention(q, k, v, causal=False) if not use_blocked else \
            _bidirectional_blocked(q, k, v)
    else:
        out = blocked_causal_attention(q, k, v, window=cfg.sliding_window)
    return project_out(p, out, x.dtype)


def _bidirectional_blocked(q, k, v, q_block: int = 1024, kv_block: int = 1024):
    """Non-causal blocked attention (encoder / cross-attention)."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    scale = 1.0 / math.sqrt(hd)
    if sq % q_block != 0:
        q_block = sq
    skv = k.shape[1]
    kb = skv if PROBE_UNROLL else min(kv_block, skv)
    if skv % kb != 0:
        kb = skv
    n_q, n_kv = sq // q_block, skv // kb

    def q_body(_, iq):
        qi = jax.lax.dynamic_slice_in_dim(q, iq * q_block, q_block, axis=1)
        m = jnp.full((b, h, q_block), -jnp.inf, jnp.float32)
        l = jnp.zeros((b, h, q_block), jnp.float32)
        acc = jnp.zeros((b, q_block, h, hd), jnp.float32)

        def kv_body(carry, ik):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(k, ik * kb, kb, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, ik * kb, kb, axis=1)
            return _attn_block(qi, ks, vs, m, l, acc, None, scale), None

        (m, l, acc), _ = jax.lax.scan(kv_body, (m, l, acc), jnp.arange(n_kv))
        return None, _finalize(acc, l)

    if PROBE_UNROLL:
        outs = jnp.stack([q_body(None, jnp.int32(i))[1] for i in range(n_q)], 0)
    else:
        _, outs = jax.lax.scan(q_body, None, jnp.arange(n_q))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_block(p, x, cfg: ModelConfig):
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype),
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype),
                       preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
    else:
        h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype),
                       preferred_element_type=jnp.float32)
        h = jax.nn.gelu(h).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)
