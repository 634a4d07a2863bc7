"""Layer library: every block kind used by the assigned architectures.

Functional style: each block kind has ``init_<kind>(key, cfg) -> params`` and
``apply_<kind>(params, cfg, x, ctx) -> (x, new_cache)``. Params are plain
dict pytrees so they stack cleanly for lax.scan over layers and shard with
simple name-based partition rules.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels.flash_attention.decode import (decode_attention,
                                                  paged_decode_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba2_scan.ops import mamba2_scan
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.models.config import AttentionConfig, ModelConfig

Params = Dict[str, Any]

# names of the matmul outputs a checkpoint policy may keep for the backward
# pass (`jax.checkpoint_policies.save_only_these_names`); untagged values
# are recomputed from them
QKV, MLP_PRE = "qkv", "mlp_pre"


# ---------------------------------------------------------------- helpers
def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               scale: float = 0.02) -> Params:
    p = {"w": scale * jax.random.normal(key, (d_in, d_out), jnp.float32)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def norm_init(d: int, kind: str) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Params, x: jnp.ndarray, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * p["scale"]).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------- RoPE
def rope_cos_sin(positions: jnp.ndarray, rot_dim: int, theta: float):
    """positions (..., S) -> cos/sin (..., S, rot_dim/2)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                             / rot_dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x (B, S, H, D); cos/sin (B, S, D/2) — rotate-half convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def mrope_cos_sin(positions: jnp.ndarray, sections: Tuple[int, int, int],
                  rot_dim: int, theta: float):
    """Qwen2-VL M-RoPE [arXiv:2409.12191]: positions (3, B, S) for
    (temporal, height, width); frequency bands are split across the three
    position streams by `sections` (in half-dim units, sum = rot_dim/2)."""
    assert sum(sections) == rot_dim // 2, (sections, rot_dim)
    cos3, sin3 = rope_cos_sin(positions, rot_dim, theta)  # (3, B, S, rot/2)
    chunks_c, chunks_s = [], []
    start = 0
    for i, sec in enumerate(sections):
        chunks_c.append(cos3[i, :, :, start:start + sec])
        chunks_s.append(sin3[i, :, :, start:start + sec])
        start += sec
    return jnp.concatenate(chunks_c, -1), jnp.concatenate(chunks_s, -1)


def sinusoidal_embedding(positions: jnp.ndarray, d: int):
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------- context
@dataclasses.dataclass
class Ctx:
    """Per-call context threaded through blocks."""
    mode: str                                   # train | prefill | decode
    positions: jnp.ndarray                      # RoPE positions: (B, S) or
    #                                             (3, B, S) for M-RoPE
    seq_pos: Optional[jnp.ndarray] = None       # (B, S) sequence indices for
    #                                             masking & cache slots (only
    #                                             differs from positions for
    #                                             M-RoPE sequences)
    impl: str = "ref"                           # attention/scan impl
    causal: bool = True                         # False: ViT / whisper encoder
    encoder_out: Optional[jnp.ndarray] = None   # (B, F, D) for cross-attn
    remat: bool = False
    unroll: bool = False                        # unroll layer scans (dry-run
    #                                             analysis: exact HLO costs)
    has_context: bool = False                   # prefill continuation: the
    #                                             cache already holds earlier
    #                                             chunks, attend over it
    #                                             (write-then-attend) instead
    #                                             of chunk-local causal

    @property
    def decoding(self) -> bool:
        return self.mode == "decode"


def _pos2d(ctx: Ctx) -> jnp.ndarray:
    return ctx.positions[0] if ctx.positions.ndim == 3 else ctx.positions


def _seq_pos(ctx: Ctx) -> jnp.ndarray:
    return ctx.seq_pos if ctx.seq_pos is not None else _pos2d(ctx)


# ---------------------------------------------------------------- attention
def init_attention(key, cfg: ModelConfig, *, cross: bool = False) -> Params:
    att = cfg.attention
    D = cfg.d_model
    ks = jax.random.split(key, 8)
    p: Params = {"ln": norm_init(D, cfg.norm)}
    if att.mla is not None:
        m = att.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        p["q_a"] = dense_init(ks[0], D, m.q_lora_rank)
        p["q_a_ln"] = norm_init(m.q_lora_rank, "rmsnorm")
        p["q_b"] = dense_init(ks[1], m.q_lora_rank, att.n_heads * qk)
        p["kv_a"] = dense_init(ks[2], D, m.kv_lora_rank + m.qk_rope_head_dim)
        p["kv_a_ln"] = norm_init(m.kv_lora_rank, "rmsnorm")
        p["kv_b"] = dense_init(
            ks[3], m.kv_lora_rank,
            att.n_heads * (m.qk_nope_head_dim + m.v_head_dim))
        p["o"] = dense_init(ks[4], att.n_heads * m.v_head_dim, D)
    else:
        p["q"] = dense_init(ks[0], D, att.n_heads * att.head_dim, bias=att.qkv_bias)
        p["k"] = dense_init(ks[1], D, att.n_kv_heads * att.head_dim, bias=att.qkv_bias)
        p["v"] = dense_init(ks[2], D, att.n_kv_heads * att.head_dim, bias=att.qkv_bias)
        p["o"] = dense_init(ks[3], att.n_heads * att.head_dim, D)
    if cross:
        p["ln_cross"] = norm_init(D, cfg.norm)
    return p


def init_attn_cache(cfg: ModelConfig, batch: int, window: int,
                    dtype=jnp.float32) -> Params:
    att = cfg.attention
    if att.mla is not None:
        m = att.mla
        return {
            "ckv": jnp.zeros((batch, window, m.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, window, m.qk_rope_head_dim), dtype),
            "positions": jnp.full((batch, window), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, window, att.n_kv_heads, att.head_dim), dtype),
        "v": jnp.zeros((batch, window, att.n_kv_heads, att.head_dim), dtype),
        "positions": jnp.full((batch, window), -1, jnp.int32),
    }


def _cache_write(cache: Params, names: Tuple[str, ...], values, pos: jnp.ndarray):
    """Ring-buffer write of one decode step at absolute position `pos` (B,)."""
    window = cache["positions"].shape[1]
    slot = pos % window                                  # (B,)
    out = dict(cache)
    for name, val in zip(names, values):
        # val (B, 1, ...) -> write into slot per batch row
        b_idx = jnp.arange(val.shape[0])
        out[name] = cache[name].at[b_idx, slot].set(val[:, 0])
    out["positions"] = cache["positions"].at[jnp.arange(pos.shape[0]), slot].set(pos)
    return out


def _paged_cache_write(cache: Params, names: Tuple[str, ...], values,
                       pos: jnp.ndarray):
    """One decode token into a paged pool: slot b's token at absolute
    position `pos[b]` lands at offset pos % page_size of physical page
    block_tables[b, pos // page_size]. Idle slots' tables point every block
    at the scratch page, so their (discarded) writes never touch a live
    page; duplicate scratch writes are fine because scratch is never read."""
    bt = cache["block_tables"]                           # (S, n_blocks)
    page_len = cache["positions"].shape[1]
    b_idx = jnp.arange(pos.shape[0])
    page = bt[b_idx, pos // page_len]                    # (B,)
    off = pos % page_len
    out = dict(cache)
    for name, val in zip(names, values):
        out[name] = cache[name].at[page, off].set(val[:, 0])
    out["positions"] = cache["positions"].at[page, off].set(pos)
    return out


def _gqa_attend(q, k, v, ctx: Ctx, att: AttentionConfig, *, window, softcap,
                kv_positions=None, q_offset=None, causal=True, scale=None):
    return flash_attention(
        q, k, v, causal=causal, q_offset=q_offset, kv_positions=kv_positions,
        sliding_window=window, softcap=softcap, scale=scale, impl=ctx.impl)


def apply_attention(p: Params, cfg: ModelConfig, x: jnp.ndarray, ctx: Ctx,
                    cache: Optional[Params], *, kind: str = "attn"):
    """Self-attention block half (pre-norm). Returns (residual_delta, cache)."""
    att = cfg.attention
    B, S, D = x.shape
    h = apply_norm(p["ln"], x, cfg.norm)
    window = att.sliding_window if kind == "attn_local" else None
    pos2d = _pos2d(ctx)
    sp = _seq_pos(ctx)

    if att.mla is not None:
        return _apply_mla(p, cfg, x, h, ctx, cache, window)

    q = checkpoint_name(dense(p["q"], h), QKV).reshape(
        B, S, att.n_heads, att.head_dim)
    k = checkpoint_name(dense(p["k"], h), QKV).reshape(
        B, S, att.n_kv_heads, att.head_dim)
    v = checkpoint_name(dense(p["v"], h), QKV).reshape(
        B, S, att.n_kv_heads, att.head_dim)

    if att.use_rope:
        if att.mrope_sections is not None and ctx.positions.ndim == 3:
            cos, sin = mrope_cos_sin(ctx.positions, att.mrope_sections,
                                     att.head_dim, att.rope_theta)
        else:
            cos, sin = rope_cos_sin(pos2d, att.head_dim, att.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_cache = cache
    if ctx.mode == "decode":
        if cache is not None and "block_tables" in cache:
            # paged decode: write through the block table, then attend the
            # slot's pages (gather on XLA, scalar-prefetch on TPU Pallas)
            new_cache = _paged_cache_write(cache, ("k", "v"), (k, v),
                                           sp[:, 0])
            out = paged_decode_attention(
                q, new_cache["k"], new_cache["v"],
                block_tables=cache["block_tables"], q_positions=sp[:, 0],
                kv_positions=new_cache["positions"], sliding_window=window,
                softcap=att.attn_logit_softcap, impl=ctx.impl)
        else:
            # decode fast path: single-query cache-read kernel, never the
            # full flash machinery (see kernels/flash_attention/decode.py)
            new_cache = _cache_write(cache, ("k", "v"), (k, v), sp[:, 0])
            out = decode_attention(
                q, new_cache["k"], new_cache["v"], q_positions=sp[:, 0],
                kv_positions=new_cache["positions"], sliding_window=window,
                softcap=att.attn_logit_softcap, impl=ctx.impl)
    elif ctx.has_context and cache is not None:
        # chunked-prefill continuation: land this chunk's K/V in the cache
        # first, then attend over everything cached so far (earlier chunks
        # + this one) with absolute query positions
        w = cache["positions"].shape[1]
        slot = sp % w
        b_idx = jnp.arange(B)[:, None]
        new_cache = dict(cache)
        new_cache["k"] = cache["k"].at[b_idx, slot].set(k)
        new_cache["v"] = cache["v"].at[b_idx, slot].set(v)
        new_cache["positions"] = cache["positions"].at[b_idx, slot].set(sp)
        out = _gqa_attend(q, new_cache["k"], new_cache["v"], ctx, att,
                          window=window, softcap=att.attn_logit_softcap,
                          kv_positions=new_cache["positions"],
                          q_offset=sp[:, 0])
    else:
        out = _gqa_attend(q, k, v, ctx, att, window=window,
                          softcap=att.attn_logit_softcap, causal=ctx.causal)
        if ctx.mode == "prefill" and cache is not None:
            w = cache["positions"].shape[1]
            keep = min(w, S)
            new_cache = dict(cache)
            # store last `keep` tokens at slots pos % w (ring layout)
            tail_pos = sp[:, S - keep:]
            slot = tail_pos % w
            b_idx = jnp.arange(B)[:, None]
            new_cache["k"] = cache["k"].at[b_idx, slot].set(k[:, S - keep:])
            new_cache["v"] = cache["v"].at[b_idx, slot].set(v[:, S - keep:])
            new_cache["positions"] = cache["positions"].at[b_idx, slot].set(tail_pos)

    out = out.reshape(B, S, att.n_heads * att.head_dim)
    return dense(p["o"], out), new_cache


def _apply_mla(p: Params, cfg: ModelConfig, x, h, ctx: Ctx, cache, window):
    """DeepSeek-V3 Multi-head Latent Attention. The decode cache holds only
    the compressed latent (kv_lora + rope dims) — the memory win that makes
    long decode caches cheap."""
    att = cfg.attention
    m = att.mla
    if cache is not None and "block_tables" in cache:
        raise NotImplementedError(
            "paged KV cache does not support MLA latent caches")
    B, S, D = x.shape
    pos2d = _pos2d(ctx)
    sp = _seq_pos(ctx)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim

    q = dense(p["q_b"], apply_norm(p["q_a_ln"], dense(p["q_a"], h), "rmsnorm"))
    q = q.reshape(B, S, att.n_heads, qk)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]

    kv_a = dense(p["kv_a"], h)
    ckv = apply_norm(p["kv_a_ln"], kv_a[..., :m.kv_lora_rank], "rmsnorm")
    k_rope = kv_a[..., m.kv_lora_rank:]                     # (B, S, rope)

    cos, sin = rope_cos_sin(pos2d, m.qk_rope_head_dim, att.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)    # (B, S, 1, rope)

    def decompress(ckv_seq):
        kv = dense(p["kv_b"], ckv_seq)
        kv = kv.reshape(*ckv_seq.shape[:-1], att.n_heads,
                        m.qk_nope_head_dim + m.v_head_dim)
        return kv[..., :m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]

    new_cache = cache
    scale = qk ** -0.5
    if ctx.mode == "decode":
        new_cache = _cache_write(cache, ("ckv", "kr"),
                                 (ckv, k_rope[:, :, 0]), sp[:, 0])
        k_nope, v = decompress(new_cache["ckv"])            # (B, W, H, ·)
        kr = jnp.broadcast_to(
            new_cache["kr"][:, :, None, :],
            (*new_cache["kr"].shape[:2], att.n_heads, m.qk_rope_head_dim))
        k = jnp.concatenate([k_nope, kr], -1)
        qfull = jnp.concatenate([q_nope, q_rope], -1)
        out = decode_attention(
            qfull, k, v, q_positions=sp[:, 0],
            kv_positions=new_cache["positions"], sliding_window=window,
            softcap=att.attn_logit_softcap, scale=scale, impl=ctx.impl)
    else:
        k_nope, v = decompress(ckv)
        kr = jnp.broadcast_to(k_rope, (B, S, att.n_heads, m.qk_rope_head_dim))
        k = jnp.concatenate([k_nope, kr], -1)
        qfull = jnp.concatenate([q_nope, q_rope], -1)
        out = flash_attention(qfull, k, v, causal=True, sliding_window=window,
                              softcap=att.attn_logit_softcap, scale=scale,
                              impl=ctx.impl)
        if ctx.mode == "prefill" and cache is not None:
            w = cache["positions"].shape[1]
            keep = min(w, S)
            tail_pos = sp[:, S - keep:]
            slot = tail_pos % w
            b_idx = jnp.arange(B)[:, None]
            new_cache = dict(cache)
            new_cache["ckv"] = cache["ckv"].at[b_idx, slot].set(ckv[:, S - keep:])
            new_cache["kr"] = cache["kr"].at[b_idx, slot].set(
                k_rope[:, S - keep:, 0])
            new_cache["positions"] = cache["positions"].at[b_idx, slot].set(tail_pos)

    out = out.reshape(B, S, att.n_heads * m.v_head_dim)
    return dense(p["o"], out), new_cache


def apply_cross_attention(p: Params, cfg: ModelConfig, x, ctx: Ctx):
    """Cross-attention to ctx.encoder_out (whisper decoder)."""
    att = cfg.attention
    B, S, D = x.shape
    h = apply_norm(p["ln_cross"], x, cfg.norm)
    enc = ctx.encoder_out
    q = dense(p["cq"], h).reshape(B, S, att.n_heads, att.head_dim)
    k = dense(p["ck"], enc).reshape(B, enc.shape[1], att.n_kv_heads, att.head_dim)
    v = dense(p["cv"], enc).reshape(B, enc.shape[1], att.n_kv_heads, att.head_dim)
    out = flash_attention(q, k, v, causal=False, impl=ctx.impl)
    return dense(p["co"], out.reshape(B, S, -1))


# ---------------------------------------------------------------- MLP
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"ln": norm_init(D, cfg.norm)}
    if cfg.mlp_activation.endswith("_glu"):
        p["up"] = dense_init(ks[0], D, F)
        p["gate"] = dense_init(ks[1], D, F)
    else:
        p["up"] = dense_init(ks[0], D, F)
    p["down"] = dense_init(ks[2], F, D)
    return p


def _act(x, kind: str):
    if kind.startswith("gelu"):
        return jax.nn.gelu(x)
    if kind.startswith("silu"):
        return jax.nn.silu(x)
    if kind == "relu2":  # nemotron-4 squared ReLU [arXiv:2402.16819]
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


def apply_mlp(p: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    h = apply_norm(p["ln"], x, cfg.norm)
    if cfg.mlp_activation.endswith("_glu"):
        h = (_act(checkpoint_name(dense(p["gate"], h), MLP_PRE),
                  cfg.mlp_activation)
             * checkpoint_name(dense(p["up"], h), MLP_PRE))
    else:
        h = _act(checkpoint_name(dense(p["up"], h), MLP_PRE),
                 cfg.mlp_activation)
    return dense(p["down"], h)


# ---------------------------------------------------------------- MoE
@jax.custom_vjp
def _ragged_dot(lhs, rhs, group_sizes):
    """ragged_dot with a custom VJP: the built-in transpose rule produces a
    ragged op whose vmap rule is NYI (breaks grad-under-client-vmap).
    dlhs is another dim-0 ragged_dot (vmap-safe); drhs is a segment
    scatter-add — only materialized when expert weights are actually being
    differentiated (full-FT baselines; DCE'd for SFPrompt's frozen body)."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _ragged_dot_fwd(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _ragged_dot_bwd(res, dout):
    lhs, rhs, gs = res
    M = lhs.shape[0]
    G = rhs.shape[0]
    dlhs = jax.lax.ragged_dot(dout, jnp.swapaxes(rhs, 1, 2), gs)
    ids = jnp.repeat(jnp.arange(G), gs, total_repeat_length=M)
    drhs = jnp.zeros_like(rhs).at[ids].add(
        lhs[:, :, None] * dout[:, None, :])
    dgs = jnp.zeros(gs.shape, dtype=jax.dtypes.float0)
    return dlhs.astype(lhs.dtype), drhs, dgs


_ragged_dot.defvjp(_ragged_dot_fwd, _ragged_dot_bwd)


def init_moe(key, cfg: ModelConfig) -> Params:
    e = cfg.moe
    D, F, E = cfg.d_model, e.d_ff_expert, e.n_experts
    ks = jax.random.split(key, 6)
    s = 0.02
    p = {
        "ln": norm_init(D, cfg.norm),
        "router": dense_init(ks[0], D, E),
        "experts": {
            "up": s * jax.random.normal(ks[1], (E, D, F), jnp.float32),
            "gate": s * jax.random.normal(ks[2], (E, D, F), jnp.float32),
            "down": s * jax.random.normal(ks[3], (E, F, D), jnp.float32),
        },
    }
    if e.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=F * e.n_shared_experts)
        del p["shared"]["ln"]  # share the block norm
    return p


def apply_moe(p: Params, cfg: ModelConfig, x: jnp.ndarray):
    """Token-choice top-k MoE with DROPLESS sort-based dispatch.

    Tokens are sorted by expert assignment and pushed through
    jax.lax.ragged_dot (grouped GEMM — the megablocks pattern, MXU-native):
    FLOPs scale with *activated* expert paths (N*top_k), not E, keeping
    dry-run cost_analysis honest for 256-expert stacks, and no token is ever
    dropped, so decode and train routing agree exactly.
    Returns (delta, aux) where aux carries the load-balance loss.
    """
    e = cfg.moe
    B, S, D = x.shape
    N = B * S
    h = apply_norm(p["ln"], x, cfg.norm)
    flat = h.reshape(N, D)

    logits = dense(p["router"], flat).astype(jnp.float32)     # (N, E)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, e.top_k)              # (N, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], e.n_experts), 0)
    router_mean = jnp.mean(probs, 0)
    aux = e.load_balance_coef * e.n_experts * jnp.sum(density * router_mean)

    flat_e = top_e.reshape(-1)                                 # (N*k,)
    order = jnp.argsort(flat_e)                                # stable
    tok = order // e.top_k                                     # token per slot
    group_sizes = jnp.bincount(flat_e, length=e.n_experts).astype(jnp.int32)

    xs = flat[tok]                                             # (N*k, D) sorted
    # keep the dispatched tokens in the residual-stream layout (hidden dim
    # over 'model'): without this SPMD flip-flops between layouts around the
    # gather and inserts an involuntary full all-gather per MoE layer
    # (EXPERIMENTS.md #Perf pair B, iteration 2).
    try:
        from jax.sharding import PartitionSpec as _P
        mesh = jax.sharding.get_abstract_mesh()
        if (mesh is not None and "model" in dict(getattr(mesh, "shape", {}))
                and D % dict(mesh.shape)["model"] == 0):
            xs = jax.lax.with_sharding_constraint(xs, _P(None, "model"))
    except Exception:
        pass  # no mesh context (single-device CPU tests)
    we = p["experts"]
    gate = _ragged_dot(xs, we["gate"].astype(h.dtype), group_sizes)
    up = _ragged_dot(xs, we["up"].astype(h.dtype), group_sizes)
    hid = _act(gate, "silu_glu") * up
    ys = _ragged_dot(hid, we["down"].astype(h.dtype), group_sizes)

    gathered = ys * top_p.reshape(-1)[order][:, None].astype(h.dtype)
    y = jnp.zeros((N, D), h.dtype).at[tok].add(gathered)

    if "shared" in p:
        sh = p["shared"]
        hshared = _act(dense(sh["gate"], h), "silu_glu") * dense(sh["up"], h)
        y = y.reshape(B, S, D) + dense(sh["down"], hshared)
        return y, aux
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------- Mamba-2
def init_mamba2(key, cfg: ModelConfig) -> Params:
    m = cfg.mamba2
    D = cfg.d_model
    di = m.d_inner(D)
    H = m.n_heads(D)
    G = 1
    conv_dim = di + 2 * G * m.d_state
    ks = jax.random.split(key, 4)
    return {
        "ln": norm_init(D, cfg.norm),
        "in_proj": dense_init(ks[0], D, 2 * di + 2 * G * m.d_state + H),
        "conv_w": 0.02 * jax.random.normal(ks[1], (m.d_conv, conv_dim), jnp.float32),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(0.01 * jnp.ones((H,), jnp.float32))),
        "norm": norm_init(di, "rmsnorm"),
        "out_proj": dense_init(ks[2], di, D),
    }


def init_mamba2_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    m = cfg.mamba2
    di = m.d_inner(cfg.d_model)
    H = m.n_heads(cfg.d_model)
    conv_dim = di + 2 * m.d_state
    return {
        "conv": jnp.zeros((batch, m.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, H, m.head_dim, m.d_state), jnp.float32),
    }


def _causal_conv1d(x, w, b, prev=None):
    """x (B, T, C); w (K, C) depthwise; prev (B, K-1, C) carried state."""
    K = w.shape[0]
    B, T, C = x.shape
    if prev is None:
        prev = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)
    out = sum(xp[:, i:i + T] * w[i].astype(x.dtype) for i in range(K))
    new_prev = xp[:, T:]
    return out + b.astype(x.dtype), new_prev


def apply_mamba2(p: Params, cfg: ModelConfig, x: jnp.ndarray, ctx: Ctx,
                 cache: Optional[Params]):
    m = cfg.mamba2
    B, S, D = x.shape
    di = m.d_inner(D)
    H = m.n_heads(D)
    G, N = 1, m.d_state
    h = apply_norm(p["ln"], x, cfg.norm)
    zxbcdt = dense(p["in_proj"], h)
    z, xin, BC, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + 2 * G * N], -1)
    conv_in = jnp.concatenate([xin, BC], -1)
    prev = cache["conv"] if cache is not None else None
    conv_out, new_prev = _causal_conv1d(conv_in, p["conv_w"], p["conv_b"], prev)
    conv_out = jax.nn.silu(conv_out)
    xin, Bm, Cm = jnp.split(conv_out, [di, di + G * N], -1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])      # (B,S,H)
    A = -jnp.exp(p["A_log"])

    xh = xin.reshape(B, S, H, m.head_dim)
    ssm_state = cache["ssm"] if cache is not None else None
    y, new_ssm = mamba2_scan(
        xh, dt, A, Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N),
        ssm_state, impl=ctx.impl)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    y = apply_norm(p["norm"], y, "rmsnorm")
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_prev, "ssm": new_ssm}
    return dense(p["out_proj"], y), new_cache


# ---------------------------------------------------------------- RWKV-6
def init_rwkv6(key, cfg: ModelConfig) -> Params:
    r6 = cfg.rwkv6
    D = cfg.d_model
    H = D // r6.head_size
    ks = jax.random.split(key, 10)
    s = 0.02
    return {
        "ln_t": norm_init(D, "layernorm"),
        "mu": s * jax.random.normal(ks[0], (5, D), jnp.float32),  # r,k,v,w,g lerps
        "w_lora_a": s * jax.random.normal(ks[1], (D, r6.decay_lora_rank), jnp.float32),
        "w_lora_b": s * jax.random.normal(ks[2], (r6.decay_lora_rank, D), jnp.float32),
        "w0": jnp.zeros((D,), jnp.float32),
        "r": dense_init(ks[3], D, D),
        "k": dense_init(ks[4], D, D),
        "v": dense_init(ks[5], D, D),
        "g": dense_init(ks[6], D, D),
        "u": s * jax.random.normal(ks[7], (H, r6.head_size), jnp.float32),
        "gn": {"scale": jnp.ones((D,), jnp.float32),
               "bias": jnp.zeros((D,), jnp.float32)},
        "o": dense_init(ks[8], D, D),
        # channel mix
        "ln_c": norm_init(D, "layernorm"),
        "mu_c": s * jax.random.normal(ks[9], (2, D), jnp.float32),
        "ck": dense_init(jax.random.fold_in(key, 101), D, cfg.d_ff),
        "cv": dense_init(jax.random.fold_in(key, 102), cfg.d_ff, D),
        "cr": dense_init(jax.random.fold_in(key, 103), D, D),
    }


def init_rwkv6_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    r6 = cfg.rwkv6
    D = cfg.d_model
    H = D // r6.head_size
    return {
        "shift_t": jnp.zeros((batch, D), dtype),
        "shift_c": jnp.zeros((batch, D), dtype),
        "state": jnp.zeros((batch, H, r6.head_size, r6.head_size), jnp.float32),
    }


def _token_shift(x, prev):
    """xx_t = x_{t-1}; first position uses carried `prev` (B, D) or zeros."""
    B, S, D = x.shape
    if prev is None:
        prev = jnp.zeros((B, D), x.dtype)
    return jnp.concatenate([prev[:, None, :], x[:, :-1]], axis=1)


def apply_rwkv6(p: Params, cfg: ModelConfig, x: jnp.ndarray, ctx: Ctx,
                cache: Optional[Params]):
    """RWKV-6 block = time-mix (data-dependent decay recurrence) +
    channel-mix, each with token-shift. [arXiv:2404.05892]"""
    r6 = cfg.rwkv6
    B, S, D = x.shape
    H, K = D // r6.head_size, r6.head_size

    # ---- time mix
    h = apply_norm(p["ln_t"], x, "layernorm")
    xx = _token_shift(h, cache["shift_t"] if cache else None)
    mix = lambda i: h + (xx - h) * p["mu"][i].astype(h.dtype)
    mr, mk, mv, mw, mg = (mix(i) for i in range(5))
    r = dense(p["r"], mr).reshape(B, S, H, K)
    k = dense(p["k"], mk).reshape(B, S, H, K)
    v = dense(p["v"], mv).reshape(B, S, H, K)
    g = jax.nn.silu(dense(p["g"], mg))
    # data-dependent decay (Finch): w = w0 + tanh(mw A) B, log-decay -exp(w)
    wdd = p["w0"] + jnp.tanh(mw.astype(jnp.float32) @ p["w_lora_a"]) @ p["w_lora_b"]
    w = -jnp.exp(wdd).reshape(B, S, H, K)

    state = cache["state"] if cache else None
    y, new_state = rwkv6_scan(r, k, v, w, p["u"], state, impl=ctx.impl)
    y = y.reshape(B, S, D)
    # per-head groupnorm
    yg = y.reshape(B, S, H, K).astype(jnp.float32)
    mu = yg.mean(-1, keepdims=True)
    var = ((yg - mu) ** 2).mean(-1, keepdims=True)
    yg = ((yg - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(B, S, D)
    y = (yg * p["gn"]["scale"] + p["gn"]["bias"]).astype(x.dtype) * g
    tdelta = dense(p["o"], y)
    x = x + tdelta

    # ---- channel mix
    hc = apply_norm(p["ln_c"], x, "layernorm")
    xxc = _token_shift(hc, cache["shift_c"] if cache else None)
    mkc = hc + (xxc - hc) * p["mu_c"][0].astype(hc.dtype)
    mrc = hc + (xxc - hc) * p["mu_c"][1].astype(hc.dtype)
    kk = jax.nn.relu(dense(p["ck"], mkc))
    cdelta = jax.nn.sigmoid(dense(p["cr"], mrc)) * dense(p["cv"], kk * kk)

    new_cache = None
    if cache is not None:
        new_cache = {"shift_t": h[:, -1], "shift_c": hc[:, -1],
                     "state": new_state}
    return tdelta + cdelta, new_cache  # caller adds to the residual stream


def init_cross_attention_extra(key, cfg: ModelConfig) -> Params:
    """Extra q/k/v/o for the cross-attention half of a decoder block."""
    att = cfg.attention
    D = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "cq": dense_init(ks[0], D, att.n_heads * att.head_dim),
        "ck": dense_init(ks[1], D, att.n_kv_heads * att.head_dim),
        "cv": dense_init(ks[2], D, att.n_kv_heads * att.head_dim),
        "co": dense_init(ks[3], att.n_heads * att.head_dim, D),
    }
