"""Block and stack library: the layers of every assigned architecture,
grouped into scanned stacks, which `core/split.py::SplitModel` assembles
into its head, body and tail segments.

A stack groups layers by the config's layer-pattern cycle and runs them
with lax.scan over stacked per-cycle parameters, so HLO size (and CPU
dry-run compile time) is independent of depth. Decode caches are stacked
the same way and threaded through the scan as xs/ys.

Modes:
  train   — full-sequence forward, no cache
  prefill — full-sequence forward, fills a (possibly ring-buffer) cache
  decode  — one token per call against the cache
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ATTN_KINDS, ModelConfig

Params = Dict[str, Any]


def _is_attn(kind: str) -> bool:
    return kind in ATTN_KINDS


# ------------------------------------------------------------------ blocks
def init_block(key, cfg: ModelConfig, kind: str) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("attn", "attn_local", "attn_global", "shared_attn"):
        return {"attn": L.init_attention(k1, cfg), "mlp": L.init_mlp(k2, cfg)}
    if kind == "cross_attn":
        p = {"attn": L.init_attention(k1, cfg, cross=True),
             "mlp": L.init_mlp(k2, cfg)}
        p["attn"].update(L.init_cross_attention_extra(k3, cfg))
        return p
    if kind == "moe":
        return {"attn": L.init_attention(k1, cfg), "moe": L.init_moe(k2, cfg)}
    if kind == "mamba2":
        return {"mamba": L.init_mamba2(k1, cfg)}
    if kind == "rwkv6":
        return {"rwkv": L.init_rwkv6(k1, cfg)}
    raise ValueError(kind)


def apply_block(params: Params, cfg: ModelConfig, kind: str, x, ctx: L.Ctx,
                cache):
    """-> (x, new_cache, aux)"""
    aux = jnp.float32(0.0)
    if kind in ("attn", "attn_local", "attn_global", "shared_attn",
                "cross_attn", "moe"):
        delta, new_cache = L.apply_attention(
            params["attn"], cfg, x, ctx, cache, kind=kind)
        x = x + delta
        if kind == "cross_attn":
            x = x + L.apply_cross_attention(params["attn"], cfg, x, ctx)
        if kind == "moe":
            delta, aux = L.apply_moe(params["moe"], cfg, x)
            x = x + delta
        else:
            x = x + L.apply_mlp(params["mlp"], cfg, x)
        return x, new_cache, aux
    if kind == "mamba2":
        delta, new_cache = L.apply_mamba2(params["mamba"], cfg, x, ctx, cache)
        return x + delta, new_cache, aux
    if kind == "rwkv6":
        delta, new_cache = L.apply_rwkv6(params["rwkv"], cfg, x, ctx, cache)
        return x + delta, new_cache, aux
    raise ValueError(kind)


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                     dtype=jnp.float32, window=None):
    """window: optional ring-buffer cap (long_500k passes
    cfg.long_context_window); local-attention layers additionally cap at
    their sliding window — their cache never needs to be larger."""
    if _is_attn(kind) or kind == "moe":
        att = cfg.attention
        eff = seq_len if window is None else min(seq_len, window)
        if kind == "attn_local" and att.sliding_window:
            eff = min(eff, att.sliding_window)
        return L.init_attn_cache(cfg, batch, max(eff, 1), dtype)
    if kind == "mamba2":
        return L.init_mamba2_cache(cfg, batch, dtype)
    if kind == "rwkv6":
        return L.init_rwkv6_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------- reusable stack runner
def init_stack(key, cfg: ModelConfig, kind: str, n: int) -> Params:
    """Stacked params for n layers of one kind (leading dim n)."""
    ks = jax.random.split(key, n)
    per = [init_block(k, cfg, kind) for k in ks]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


def run_stack(cfg: ModelConfig, stacked: Params, kinds, x, ctx: L.Ctx,
              caches=None, shared: Optional[Params] = None, policy=None):
    """Scan a stacked layer group. `stacked` maps 'pos{i}' -> stacked params
    for cycle position i; `caches` mirrors that layout (or None).
    `ctx.remat` recomputes each step whole in the backward pass; else a
    `jax.checkpoint` `policy` picks what each step keeps for it and
    recomputes the rest. Returns (x, aux, new_caches)."""
    has_cache = caches is not None

    def body(carry, xs):
        x, aux = carry
        p_cyc, c_cyc = xs if has_cache else (xs, None)
        new_caches = {}
        for i, kind in enumerate(kinds):
            p = shared if kind == "shared_attn" else p_cyc[f"pos{i}"]
            c = c_cyc[f"pos{i}"] if has_cache else None
            x, nc, a = apply_block(p, cfg, kind, x, ctx, c)
            aux = aux + a
            if has_cache:
                new_caches[f"pos{i}"] = nc
        return (x, aux), (new_caches if has_cache else None)

    if ctx.remat:
        body = jax.checkpoint(body)
    elif policy is not None:
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    xs = (stacked, caches) if has_cache else stacked
    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.float32(0.0)), xs, unroll=True if ctx.unroll else 1)
    return x, aux, new_caches


def stack_cache(cfg: ModelConfig, kind: str, n: int, batch: int,
                seq_len: int, dtype=jnp.float32, window=None):
    one = init_block_cache(cfg, kind, batch, seq_len, dtype, window=window)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)
