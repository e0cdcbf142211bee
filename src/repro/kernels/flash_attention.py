"""Pallas TPU flash-attention (prefill/train path).

Tiled online-softmax attention with causal masking, optional sliding window
and optional logit softcap (gemma-2/3), GQA-aware: KV blocks are indexed by
``q_head // group`` in the BlockSpec index_map so grouped KV heads are never
materialised ``group`` times in HBM or VMEM.

Layout: q (B, H, Sq, hd); k, v (B, K, Skv, hd).  Grid (B, H, n_q, n_kv) with
the KV axis innermost; running max / denominator / accumulator live in VMEM
scratch persisted across the innermost grid dimension (standard TPU flash
pattern).  MXU alignment: q/kv blocks are 128 rows (the whole sequence when
it is shorter).  A sequence that is not a multiple of the block (a
1024-patch prefix plus its prompt token is 1025) runs on a ``cdiv`` grid:
the last block reads past the end, its out-of-range KV columns are masked
and its out-of-range V rows zeroed, and the out-of-range query rows it
computes are never written back.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  softcap: Optional[float], q_blk: int, kv_blk: int,
                  n_kv: int, skv: int):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level skip: with causal masking, KV blocks strictly above the
    # diagonal (and, with a window, strictly below it) contribute nothing.
    row_hi = (iq + 1) * q_blk - 1
    needed = jnp.asarray(True)
    if causal:
        needed &= ikv * kv_blk <= row_hi
    if window > 0:
        row_lo = iq * q_blk
        needed &= (ikv + 1) * kv_blk - 1 > row_lo - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (q_blk, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (kv_blk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        if skv % kv_blk:
            # ragged tail block: rows past the array end hold garbage (NaN
            # in interpret mode); zero them so p == 0 cannot meet a NaN
            vrow = ikv * kv_blk + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0)
            v = jnp.where(vrow < skv, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        rows = iq * q_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ikv * kv_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < skv
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           q_blk: int = 128, kv_blk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, K, Skv, hd) → (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    kh, skv = k.shape[1], k.shape[2]
    group = h // kh
    scale = scale if scale is not None else hd ** -0.5
    # a block is either the whole axis or a multiple of 8 rows (the TPU
    # sublane tile); a ragged tail runs on a cdiv grid (module docstring)
    q_blk = sq if sq <= q_blk else q_blk - q_blk % 8
    kv_blk = skv if skv <= kv_blk else kv_blk - kv_blk % 8
    n_q, n_kv = pl.cdiv(sq, q_blk), pl.cdiv(skv, kv_blk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_blk=q_blk, kv_blk=kv_blk, n_kv=n_kv, skv=skv)

    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, hd), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, kv_blk, hd),
                         lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)),
            pl.BlockSpec((1, 1, kv_blk, hd),
                         lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, hd),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, hd), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
