"""Decode attention: one query token per slot against its KV cache.

The serving hot path. Every decode step attends a single query token per
slot to that slot's ring-buffer cache — reusing the full flash kernel there
wastes the whole q-blocking machinery on Sq=1 and (in the jnp oracle)
materializes the GQA-repeated K/V at the (B, W, Hq) footprint. This module
provides the cache-read specialization:

  impl='pallas'    — Pallas TPU kernel: grid (slots, kv_heads, kv_blocks),
                     the GQA group rides the sublane axis (group query rows
                     share their kv head's tiles), online softmax over kv
                     blocks in VMEM scratch. Masking is per-slot data:
                     kv_positions (-1 = empty slot) and the slot's absolute
                     query position, so ragged per-slot lengths, ring-buffer
                     wraparound, sliding windows, and softcap all work.
  impl='interpret' — the same kernel on the Pallas interpreter (CPU tests).
  impl='xla'       — XLA-native grouped path: einsum over (B, Hkv, G) with
                     NO materialized head repeat — the production CPU path.
  impl='ref'       — the pure-jnp oracle (`ref.attention`), bit-stable
                     with the pre-fast-path behavior.
  impl='auto'      — 'pallas' on TPU, 'xla' elsewhere.

`models/layers.py` routes every `mode="decode"` attention (GQA and MLA)
through `decode_attention` instead of the full-sequence flash call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import ref

MASK_VALUE = -2.0 ** 30
LANES = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ------------------------------------------------------------ pallas kernel
def _attend_kv_block(ikv, qp, kvp, q_ref, k_ref, v_ref, o_ref,
                     m_scr, l_scr, acc_scr, *, scale: float,
                     window: Optional[int], softcap: Optional[float],
                     n_kv_blocks: int):
    """Shared online-softmax body for one (slot, kv_head, kv_block) step:
    a (group, block_kv) score tile folded into VMEM scratch, initialized at
    the first kv block and normalized out at the last. `qp` is the slot's
    absolute query position (scalar), `kvp` the block's (1, block_kv)
    positions (-1 = empty)."""
    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (group, Dh)
    k = k_ref[0, 0].astype(jnp.float32)          # (block_kv, Dh)
    v = v_ref[0, 0].astype(jnp.float32)          # (block_kv, Dv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    # per-slot masking: cache slots are valid when they hold a real position
    # (>= 0) at or before the query's absolute position — ragged per-slot
    # lengths and ring-buffer order come in through the data, not the grid
    valid = (kvp >= 0) & (kvp <= qp)
    if window is not None:
        valid &= kvp > qp - window
    s = jnp.where(valid, s, MASK_VALUE)          # broadcast over group rows

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ikv == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                       ).astype(o_ref.dtype)


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, kvpos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float,
                   window: Optional[int], softcap: Optional[float],
                   n_kv_blocks: int):
    """One (slot, kv_head) pair; kv blocks innermost (sequential), carrying
    the online-softmax state in VMEM scratch. Block rows are the GQA group's
    query heads for this kv head — a (group, block_kv) score tile.
    `qpos_ref` is the scalar-prefetch operand of per-slot query positions."""
    _attend_kv_block(
        pl.program_id(2), qpos_ref[pl.program_id(0)], kvpos_ref[0],
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
        scale=scale, window=window, softcap=softcap, n_kv_blocks=n_kv_blocks)


def _paged_decode_kernel(bt_ref, qpos_ref, q_ref, k_ref, v_ref, kvpos_ref,
                         o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                         window: Optional[int], softcap: Optional[float],
                         n_kv_blocks: int):
    """Paged variant: the grid's kv-block axis walks the slot's BLOCK TABLE.
    `bt_ref`/`qpos_ref` are the scalar-prefetch operands — the same block
    table the in_specs index_maps used to pick this program's K/V page, so
    the kernel body only needs the slot's query position; the page indirection
    already happened in the prefetch."""
    del bt_ref  # consumed by the index_maps
    _attend_kv_block(
        pl.program_id(2), qpos_ref[pl.program_id(0)], kvpos_ref[0],
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
        scale=scale, window=window, softcap=softcap, n_kv_blocks=n_kv_blocks)


def decode_attention_fwd(
    q: jnp.ndarray,            # (B, Hkv, group, Dh) — grouped query heads
    k: jnp.ndarray,            # (B, Hkv, W, Dh)
    v: jnp.ndarray,            # (B, Hkv, W, Dv)
    q_positions: jnp.ndarray,  # (B,) int32 — absolute query position
    kv_positions: jnp.ndarray,  # (B, 1, W) int32 — -1 marks empty slots
    *,
    scale: float,
    sliding_window: Optional[int],
    softcap: Optional[float],
    block_kv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hkv, G, Dh = q.shape
    _, _, W, Dv = v.shape
    assert W % block_kv == 0, (W, block_kv)
    nkv = W // block_kv

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=sliding_window, softcap=softcap,
        n_kv_blocks=nkv)

    # the query positions ride in by scalar prefetch (SMEM), like the paged
    # kernel's: a (1, 1) VMEM block of a (B, 1) array is not a legal tile
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, ikv, qp: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, Dh),
                         lambda b, h, ikv, qp: (b, h, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, Dv),
                         lambda b, h, ikv, qp: (b, h, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv),
                         lambda b, h, ikv, qp: (b, 0, ikv)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, ikv, qp: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),   # m
            pltpu.VMEM((G, LANES), jnp.float32),   # l
            pltpu.VMEM((G, Dv), jnp.float32),      # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sfprompt_decode_attention",
    )(q_positions, q, k, v, kv_positions)


# --------------------------------------------------------------- xla path
def _xla_decode(q, k, v, q_positions, kv_positions, *, scale,
                sliding_window, softcap):
    """Grouped single-query attention without the GQA head repeat: the
    (B, W, Hkv) cache is contracted directly against (B, Hkv, G) query rows,
    so memory traffic stays at the KV-cache footprint instead of group x."""
    B, Sq, Hq, Dh = q.shape
    _, W, Hkv, Dv = v.shape
    G = Hq // Hkv
    qg = q[:, 0].reshape(B, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    valid = (kv_positions >= 0) & (kv_positions <= q_positions[:, None])
    if sliding_window is not None:
        valid &= kv_positions > q_positions[:, None] - sliding_window
    s = jnp.where(valid[:, None, None, :], s, MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, Hq, Dv).astype(q.dtype)


# --------------------------------------------------------------- public op
@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "softcap", "scale", "impl",
                     "block_kv"))
def decode_attention(
    q: jnp.ndarray,              # (B, 1, Hq, Dh) — ONE token per slot
    k: jnp.ndarray,              # (B, W, Hkv, Dh) — the slot's KV cache
    v: jnp.ndarray,              # (B, W, Hkv, Dv)
    *,
    q_positions: jnp.ndarray,    # (B,) absolute position of the query
    kv_positions: jnp.ndarray,   # (B, W) absolute positions, -1 = empty
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    block_kv: int = 128,
) -> jnp.ndarray:
    """Single-query cache-read attention for the decode hot path.

    Masking is wholly data-driven (kv validity + position vs the slot's
    query position), so ragged per-slot lengths and ring-buffer layouts need
    no host-side bookkeeping. `causal=False` is rejected: decode attention
    is causal by construction.
    """
    assert q.shape[1] == 1, f"decode_attention is single-query, got {q.shape}"
    assert causal, "decode attention is causal by construction"
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl in ("blocked", "analysis"):
        impl = "xla"   # loop-free and exact for cost analysis either way
    B, _, Hq, Dh = q.shape
    _, W, Hkv, Dv = v.shape
    if scale is None:
        scale = Dh ** -0.5
    if impl == "ref":
        return ref.attention(
            q, k, v, causal=True, q_offset=q_positions,
            kv_positions=kv_positions, sliding_window=sliding_window,
            softcap=softcap, scale=scale)
    if impl == "xla":
        return _xla_decode(q, k, v, q_positions, kv_positions, scale=scale,
                           sliding_window=sliding_window, softcap=softcap)

    G = Hq // Hkv
    bkv = min(block_kv, max(16, 1 << (W - 1).bit_length()))
    pad = (-W) % bkv
    qg = q[:, 0].reshape(B, Hkv, G, Dh)
    kt = jnp.moveaxis(k, 2, 1)                   # (B, Hkv, W, Dh)
    vt = jnp.moveaxis(v, 2, 1)
    kvp = kv_positions[:, None, :]               # (B, 1, W)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kvp = jnp.pad(kvp, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
    out = decode_attention_fwd(
        qg, kt, vt, q_positions.astype(jnp.int32), kvp.astype(jnp.int32),
        scale=scale, sliding_window=sliding_window, softcap=softcap,
        block_kv=bkv, interpret=(impl == "interpret"))
    return out.reshape(B, 1, Hq, Dv)


# ----------------------------------------------------------- paged variant
def paged_decode_attention_fwd(
    q: jnp.ndarray,             # (B, Hkv, group, Dh) — grouped query heads
    k_pool: jnp.ndarray,        # (P, Hkv, page, Dh) — the page pool
    v_pool: jnp.ndarray,        # (P, Hkv, page, Dv)
    block_tables: jnp.ndarray,  # (B, n_blocks) int32 physical page ids
    q_positions: jnp.ndarray,   # (B,) int32 — absolute query position
    kv_positions: jnp.ndarray,  # (P, 1, page) int32 — -1 marks empty
    *,
    scale: float,
    sliding_window: Optional[int],
    softcap: Optional[float],
    interpret: bool = False,
) -> jnp.ndarray:
    """The Pallas paged kernel: the block table and query positions ride in
    as scalar-prefetch operands, so the in_specs index_maps translate each
    grid step's logical block to its physical page — the kernel streams
    exactly the slot's pages out of the pool, never a gathered copy."""
    B, Hkv, G, Dh = q.shape
    P, _, page, Dv = v_pool.shape
    nb = block_tables.shape[1]

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=sliding_window,
        softcap=softcap, n_kv_blocks=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh),
                         lambda b, h, i, bt, qp: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, Dh),
                         lambda b, h, i, bt, qp: (bt[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, page, Dv),
                         lambda b, h, i, bt, qp: (bt[b, i], h, 0, 0)),
            # (1, 1, page) over (P, 1, page): the last two block dims equal
            # the array's, as the dense kernel's (B, 1, W) positions do
            pl.BlockSpec((1, 1, page),
                         lambda b, h, i, bt, qp: (bt[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, i, bt, qp: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),   # m
            pltpu.VMEM((G, LANES), jnp.float32),   # l
            pltpu.VMEM((G, Dv), jnp.float32),      # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sfprompt_paged_decode_attention",
    )(block_tables.astype(jnp.int32), q_positions.astype(jnp.int32),
      q, k_pool, v_pool, kv_positions.astype(jnp.int32))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "softcap", "scale", "impl"))
def paged_decode_attention(
    q: jnp.ndarray,              # (B, 1, Hq, Dh) — ONE token per slot
    k: jnp.ndarray,              # (P, page, Hkv, Dh) — the page POOL
    v: jnp.ndarray,              # (P, page, Hkv, Dv)
    *,
    block_tables: jnp.ndarray,   # (B, n_blocks) int32 physical page ids
    q_positions: jnp.ndarray,    # (B,) absolute position of the query
    kv_positions: jnp.ndarray,   # (P, page) absolute positions, -1 = empty
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """`decode_attention` against a PAGE POOL instead of per-slot caches.

    Slot b's KV lives in pool pages `block_tables[b]` (logical block j =
    width range [j*page, (j+1)*page)). Masking stays wholly data-driven —
    unallocated blocks point at the null page whose positions are -1, so
    they mask out exactly like empty ring slots. On the XLA/ref paths the
    pool is gathered into the dense per-slot layout (bit-identical math to
    `decode_attention` when n_blocks*page == W); on TPU the Pallas kernel
    streams pages via scalar-prefetched block tables with no gather.
    """
    assert q.shape[1] == 1, f"decode_attention is single-query, got {q.shape}"
    assert causal, "decode attention is causal by construction"
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl in ("blocked", "analysis"):
        impl = "xla"
    B, _, Hq, Dh = q.shape
    P, page, Hkv, Dv = v.shape
    nb = block_tables.shape[1]
    if scale is None:
        scale = Dh ** -0.5
    if impl in ("ref", "xla"):
        kg = k[block_tables].reshape(B, nb * page, Hkv, Dh)
        vg = v[block_tables].reshape(B, nb * page, Hkv, Dv)
        kvp = kv_positions[block_tables].reshape(B, nb * page)
        if impl == "ref":
            return ref.attention(
                q, kg, vg, causal=True, q_offset=q_positions,
                kv_positions=kvp, sliding_window=sliding_window,
                softcap=softcap, scale=scale)
        return _xla_decode(q, kg, vg, q_positions, kvp, scale=scale,
                           sliding_window=sliding_window, softcap=softcap)

    G = Hq // Hkv
    qg = q[:, 0].reshape(B, Hkv, G, Dh)
    kt = jnp.moveaxis(k, 2, 1)                   # (P, Hkv, page, Dh)
    vt = jnp.moveaxis(v, 2, 1)
    out = paged_decode_attention_fwd(
        qg, kt, vt, block_tables, q_positions, kv_positions[:, None, :],
        scale=scale, sliding_window=sliding_window, softcap=softcap,
        interpret=(impl == "interpret"))
    return out.reshape(B, 1, Hq, Dv)
