"""Flash attention as a Pallas TPU kernel.

Tiling: grid = (batch*q_heads, Sq/block_q, Skv/block_kv); the kv axis is the
innermost (sequential) dimension, carrying the online-softmax state
(running max m, denominator l, output accumulator acc) in VMEM scratch.
Block shapes are MXU-aligned (multiples of 128 on the lane dim). GQA is
handled in the BlockSpec index maps: each q head reads its kv group's block,
so kv tiles are fetched once per group member but never materialized at the
(B, Sq, Hq) footprint.

Supports causal masking, sliding windows (gemma-2 local layers / ring-buffer
long-context decode prefill), logit softcapping, and right-padded kv.
Self-correcting masked-softmax: fully-masked rows produce garbage that is
annihilated by alpha=exp(m_prev - m_new)=0 once a real logit arrives.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


MASK_VALUE = -2.0 ** 30
LANES = 128


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: Optional[int],
               softcap: Optional[float], kv_len: int,
               block_q: int, block_kv: int, n_kv_blocks: int):
    iq = pl.program_id(1)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level skip: a kv block is dead if it is entirely above the causal
    # diagonal or entirely left of every row's sliding window.
    row_min = iq * block_q
    row_max = iq * block_q + block_q - 1
    col_min = ikv * block_kv
    col_max = ikv * block_kv + block_kv - 1
    live = jnp.asarray(True)
    if causal:
        live &= col_min <= row_max
    if window is not None:
        live &= col_max > row_min - window
    live &= col_min < kv_len

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0].astype(jnp.float32)          # (block_kv, d)
        v = v_ref[0].astype(jnp.float32)          # (block_kv, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        rows = row_min + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        cols = col_min + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = cols < kv_len
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_scr[:, :1]                      # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ikv == n_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,   # (BHq, Sq, Dh) — batch and q-heads flattened
    k: jnp.ndarray,   # (BHkv, Skv, Dh)
    v: jnp.ndarray,   # (BHkv, Skv, Dv)
    *,
    n_q_heads: int,
    n_kv_heads: int,
    causal: bool,
    sliding_window: Optional[int],
    softcap: Optional[float],
    scale: float,
    kv_len: int,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    BH, Sq, Dh = q.shape
    _, Skv, Dv = v.shape
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv, block_q, block_kv)
    group = n_q_heads // n_kv_heads
    nq, nkv = Sq // block_q, Skv // block_kv

    def q_index(bh, iq, ikv):
        return (bh, iq, 0)

    def kv_index(bh, iq, ikv):
        b, h = bh // n_q_heads, bh % n_q_heads
        return (b * n_kv_heads + h // group, ikv, 0)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=sliding_window,
        softcap=softcap, kv_len=kv_len, block_q=block_q, block_kv=block_kv,
        n_kv_blocks=nkv)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), q_index),
            pl.BlockSpec((1, block_kv, Dh), kv_index),
            pl.BlockSpec((1, block_kv, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), q_index),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # m
            pltpu.VMEM((block_q, LANES), jnp.float32),   # l
            pltpu.VMEM((block_q, Dv), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sfprompt_flash_attention",
    )(q, k, v)
