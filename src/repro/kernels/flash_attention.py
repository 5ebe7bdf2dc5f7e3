"""Pallas TPU flash attention (causal GQA, optional sliding window).

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — the kv axis is minor-most,
so the VMEM scratch (running max m, normalizer l, accumulator acc) persists
across kv steps of one (b, h, q_block) tile; the output tile is written on the
last kv step. Block shapes keep the working set in VMEM:
  q tile  [block_q, hd]   k/v tiles [block_kv, hd]   acc [block_q, hd] f32
with MXU-aligned block_q/block_kv (multiples of 128) and f32 accumulation.

GQA: the kv BlockSpec index_map folds the query head onto its kv head
(h // group_size), so no repeated K/V ever materializes.

Causality/window: kv blocks entirely in the future are skipped by masking;
fully-masked tiles still execute (TPU grids are dense) but contribute zero —
the ops.py wrapper additionally shrinks the kv grid to the causal hull.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, block_q: int, block_kv: int,
                  causal: bool, window: Optional[int], kv_len: int):
    qb = pl.program_id(2)
    kvb = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kvb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)          # [block_q, hd]
    k = k_ref[...].astype(jnp.float32)          # [block_kv, hd]
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_kv), 0)
    k_pos = kvb * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_kv), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                          # [block_q, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                       # [block_q, block_kv]
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kvb == n_kv - 1)
    def _emit():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,           # [B, T, H, hd]
    k: jax.Array,           # [B, S, Hkv, hd]
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, t, h, hd = q.shape
    s = k.shape[1]
    h_kv = k.shape[2]
    assert h % h_kv == 0
    group = h // h_kv
    assert t % block_q == 0 and s % block_kv == 0
    sm_scale = hd ** -0.5

    grid = (b, h, t // block_q, s // block_kv)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_q=block_q, block_kv=block_kv,
        causal=causal, window=window, kv_len=s)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, None, hd),
                         lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((None, block_kv, None, hd),
                         lambda bi, hi, qi, ki, g=group: (bi, ki, hi // g, 0)),
            pl.BlockSpec((None, block_kv, None, hd),
                         lambda bi, hi, qi, ki, g=group: (bi, ki, hi // g, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, None, hd),
                               lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
