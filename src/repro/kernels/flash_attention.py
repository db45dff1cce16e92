"""Pallas TPU kernel: blockwise causal flash attention (forward).

Grid (batch*heads, q_blocks, kv_blocks); the kv dimension is innermost so
the online-softmax running state (m, l, acc) lives in VMEM scratch across
kv steps (TPU grid steps execute sequentially per core). Causal skipping:
kv blocks entirely in the future contribute nothing — the whole body runs
under pl.when(kv_start <= q_end), which on real TPUs skips the compute
(this is where the jnp reference's masked-FLOP waste disappears).

GQA: k/v carry KH heads; the q-head -> kv-head mapping happens in the
BlockSpec index_map (h // group), so kv blocks are never materially
repeated — unlike the XLA path, which broadcasts kv to H heads.

Block shapes default to (128, 128): MXU-aligned, and the VMEM working set is
q(128xD) + k,v(128xD) + acc(128xD) + scores(128x128) ~ 0.5 MB for D=128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bq: int, bk: int, scale: float, causal: bool):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    run = (k_start <= q_start + bq - 1) if causal else (ik >= 0)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Skv, KH, D) with H % KH == 0.

    Returns (B, Sq, H, D) in q.dtype. Forward only — the training path uses
    the XLA blocked implementation (repro.models.layers); this kernel is the
    serving/prefill hot path and the roofline subject.
    """
    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    assert h % kh == 0, (h, kh)
    group = h // kh
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    if sq % bq:
        bq = sq
    if skv % bk:
        bk = skv

    # (B*H, S, D) layout; kv keeps KH heads, mapped via index_map
    qr = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kr = jnp.moveaxis(k, 2, 1).reshape(b * kh, skv, d)
    vr = jnp.moveaxis(v, 2, 1).reshape(b * kh, skv, d)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        return ((bh // h) * kh + (bh % h) // group, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk,
                          scale=1.0 / math.sqrt(d), causal=causal),
        grid=(b * h, sq // bq, skv // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return jnp.moveaxis(out.reshape(b, h, sq, d), 1, 2)
