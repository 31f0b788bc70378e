"""Flash-attention Pallas TPU kernel (FlashAttention [arXiv:2205.14135]
re-blocked for the TPU memory hierarchy).

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv axis sequential
("arbitrary") — the online-softmax state (m, l, acc) lives in VMEM scratch
across kv steps, exactly the paper's receptive-field tiling re-derived for
VMEM: a (block_q x d) query tile stays resident while (block_kv x d) K/V
tiles stream through.

Supports causal, sliding-window, chunked-local masking and GQA (K/V block
index maps fold q_head -> kv_head), plus a query position offset for
cache-relative decode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale, block_q, block_kv, causal, window, chunk, q_offset,
                 kv_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = (q_offset + qi * block_q
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0))
    k_pos = (ki * block_kv
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1))
    mask = k_pos < kv_len                                   # padding
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    if chunk:
        mask &= (k_pos // chunk) == (q_pos // chunk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = (acc_scr[...] * corr[:, None]
                    + jax.lax.dot(p, v,
                                  preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal=True, window=0, chunk=0,
                           q_offset=0, block_q=128, block_kv=128,
                           interpret=False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D).

    Sq/Skv are padded to block multiples; padded keys are masked via
    ``kv_len``; padded queries produce garbage rows the wrapper slices off.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, max(Sq, 8))
    block_kv = min(block_kv, max(Skv, 8))

    pq = -Sq % block_q
    pk = -Skv % block_kv
    kv_len = Skv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_kv

    grid = (B, Hq, nq, nk)
    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal, window=window, chunk=chunk, q_offset=q_offset,
        kv_len=kv_len)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m
            pltpu.VMEM((block_q,), jnp.float32),      # l
            pltpu.VMEM((block_q, D), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
