"""Pallas TPU flash-attention kernel (causal GQA, online softmax).

TPU-native design (not a CUDA port):
  * grid = (batch·q_heads, q_blocks, kv_blocks) — the kv dimension is the
    innermost sequential grid axis, so the (m, l, acc) accumulators live in
    VMEM scratch across kv steps (revisiting semantics), exactly where the
    MXU wants its operands;
  * BlockSpecs tile Q (BLOCK_Q × head_dim) and K/V (BLOCK_KV × head_dim)
    into VMEM; head_dim and block sizes are multiples of 128 (MXU/VREG
    alignment) whenever the model's head_dim allows;
  * GQA is expressed in the K/V index_map (kv_head = q_head // group), so
    grouped heads reuse the same K/V tiles without materializing repeats;
  * the causal mask is generated from block indices with iota — no mask
    tensors stream from HBM.

Validated in interpret mode against ``ref.reference_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_kv: int, causal: bool,
                  seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    kv_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)

    run = True
    if causal:
        # whole block strictly above the diagonal contributes nothing
        run = (ki * block_kv) <= (qi * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # (BQ, hd)
        k = k_ref[0].astype(jnp.float32)            # (BKV, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = kv_pos < seq_len                      # KV padding
        if causal:
            mask &= kv_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                          # (BQ, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    softmax_scale: Optional[float] = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, hd); k/v: (B, Hkv, T, hd) with Hq % Hkv == 0."""
    b, hq, s, hd = q.shape
    t, hkv = k.shape[2], k.shape[1]
    group = hq // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)

    block_q = min(block_q, s)
    block_kv = min(block_kv, t)
    s_pad = -(-s // block_q) * block_q
    t_pad = -(-t // block_kv) * block_kv
    if s_pad != s:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, s_pad - s), (0, 0)])
    if t_pad != t:
        k = jnp.pad(k, [(0, 0), (0, 0), (0, t_pad - t), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, 0), (0, t_pad - t), (0, 0)])

    qf = q.reshape(b * hq, s_pad, hd)
    kf = k.reshape(b * hkv, t_pad, hd)
    vf = v.reshape(b * hkv, t_pad, hd)

    grid = (b * hq, s_pad // block_q, t_pad // block_kv)

    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_kv=block_kv, causal=causal, seq_len=t)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, hd),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_kv, hd),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),    # l (running denominator)
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc (weighted values)
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, s_pad, hd)[:, :, :s]
