"""Transformer assembly: config-driven stacks for all assigned architectures.

The layer stack is grouped by the config's layer-pattern cycle and executed
with lax.scan over stacked per-cycle parameters, so HLO size (and CPU
dry-run compile time) is independent of depth. Decode caches are stacked the
same way and threaded through the scan as xs/ys.

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
    """-> (x, new_cache, aux_loss)"""
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
    recomputes the rest. Returns (x, aux_loss, new_caches)."""
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


# ------------------------------------------------------------------ model
class Transformer:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---------------- init
    def init(self, key) -> Params:
        cfg = self.cfg
        keys = iter(jax.random.split(key, 64))
        params: Params = {}
        if cfg.arch_type == "vit":
            patch_dim = 16 * 16 * 3
            params["embed"] = {
                "patch": L.dense_init(next(keys), patch_dim, cfg.d_model),
                "cls": 0.02 * jax.random.normal(next(keys), (1, cfg.d_model)),
                "pos": 0.02 * jax.random.normal(
                    next(keys), (cfg.max_seq_len, cfg.d_model)),
            }
        else:
            params["embed"] = {
                "tok": 0.02 * jax.random.normal(
                    next(keys), (cfg.vocab_size, cfg.d_model), jnp.float32)}

        if cfg.n_dense_layers:
            params["dense_stack"] = {"pos0": self._init_stack(
                next(keys), ("attn",), cfg.n_dense_layers)}

        # one stacked group per cycle position
        cyc = {}
        for i, kind in enumerate(cfg.layer_pattern):
            if kind == "shared_attn":
                continue  # weights shared, initialized once below
            cyc[f"pos{i}"] = self._init_stack(
                next(keys), (kind,), cfg.n_cycles)
        params["cycle"] = cyc
        if "shared_attn" in cfg.layer_pattern:
            params["shared_attn"] = init_block(next(keys), cfg, "shared_attn")

        params["final_norm"] = L.norm_init(cfg.d_model, cfg.norm)
        out_dim = cfg.num_classes or cfg.vocab_size
        if not cfg.tie_embeddings or cfg.num_classes:
            params["head"] = L.dense_init(next(keys), cfg.d_model, out_dim)

        if cfg.encoder is not None:
            enc = {"cycle": {"pos0": self._init_stack(
                next(keys), ("attn",), cfg.encoder.n_layers)},
                "final_norm": L.norm_init(cfg.d_model, cfg.norm)}
            params["encoder"] = enc
        if cfg.mtp:
            params["mtp"] = {
                "proj": L.dense_init(next(keys), 2 * cfg.d_model, cfg.d_model),
                "block": init_block(next(keys), cfg, "attn"),
                "norm": L.norm_init(cfg.d_model, cfg.norm),
            }
        return params

    def _init_stack(self, key, kinds, n: int) -> Params:
        return init_stack(key, self.cfg, kinds[0], n)

    # ---------------- caches
    def init_cache(self, batch: int, seq_len: int, dtype=jnp.float32,
                   window=None) -> Params:
        cfg = self.cfg
        cache: Params = {"cycle": {}}

        def stack(kind, n):
            one = init_block_cache(cfg, kind, batch, seq_len, dtype,
                                   window=window)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)

        if cfg.n_dense_layers:
            cache["dense_stack"] = {"pos0": stack("attn", cfg.n_dense_layers)}
        for i, kind in enumerate(cfg.layer_pattern):
            cache["cycle"][f"pos{i}"] = stack(kind, cfg.n_cycles)
        if cfg.encoder is not None:
            cache["encoder_out"] = jnp.zeros(
                (batch, cfg.encoder.n_frames, cfg.d_model), dtype)
        return cache

    # ---------------- scan over a homogeneous stacked group
    def _run_stack(self, stacked: Params, kinds, x, ctx: L.Ctx, caches,
                   shared: Optional[Params] = None):
        return run_stack(self.cfg, stacked, kinds, x, ctx, caches,
                         shared=shared)

    # ---------------- embedding frontends
    def _embed(self, params, batch: Dict[str, jnp.ndarray], ctx_mode: str,
               prompts: Optional[jnp.ndarray], dtype):
        """Returns (x, positions, n_prefix) — n_prefix = prompt+patch tokens."""
        cfg = self.cfg
        emb = params["embed"]

        if cfg.arch_type == "vit":
            patches = batch["patches"]                         # (B, N, ppc)
            B = patches.shape[0]
            x = L.dense(emb["patch"], patches)
            cls = jnp.broadcast_to(emb["cls"][None], (B, 1, cfg.d_model))
            x = jnp.concatenate([cls.astype(x.dtype), x], 1)
            if prompts is not None:
                pr = jnp.broadcast_to(prompts[None], (B,) + prompts.shape)
                x = jnp.concatenate([x[:, :1], pr.astype(x.dtype), x[:, 1:]], 1)
            x = x + emb["pos"][: x.shape[1]].astype(x.dtype)
            pos = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32)[None],
                                   x.shape[:2])
            return x, pos, pos, 0

        toks = batch["tokens"]
        B, S = toks.shape
        x = jnp.take(emb["tok"].astype(dtype), toks, axis=0)
        n_prefix = 0
        if cfg.arch_type == "audio":
            # whisper decoder: absolute positions, no RoPE
            if ctx_mode == "decode":
                apos = batch["pos"][:, None]
            else:
                apos = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None], (B, S))
            x = x + L.sinusoidal_embedding(apos, cfg.d_model).astype(dtype)

        if cfg.arch_type == "vlm" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(dtype)           # (B, Np, D)
            x = jnp.concatenate([pe, x], axis=1)
            n_prefix += pe.shape[1]

        if prompts is not None and ctx_mode != "decode":
            pr = jnp.broadcast_to(prompts[None], (B,) + prompts.shape)
            x = jnp.concatenate([pr.astype(dtype), x], axis=1)
            n_prefix += prompts.shape[0]

        T = x.shape[1]
        if ctx_mode == "decode":
            base = batch["pos"][:, None]                       # (B, 1)
        else:
            base = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        base = base.astype(jnp.int32)

        att = cfg.attention
        if att is not None and att.mrope_sections is not None:
            # M-RoPE: layout is [prompt | patches | text]. Patches carry
            # (t, h, w) grid positions from the frontend stub (offset past
            # the prompts); prompt/text stream positions are the sequence
            # index on all three channels. Masking & cache slots use `base`.
            if ctx_mode != "decode" and "mrope_positions" in batch:
                # stored client-axis-first as (B, 3, Np); model wants (3, B, Np)
                grid = jnp.moveaxis(
                    batch["mrope_positions"], 1, 0).astype(jnp.int32)
                npz = grid.shape[-1]
                npr = n_prefix - npz
                b3 = jnp.broadcast_to(base[None], (3, B, T))
                pos = jnp.concatenate(
                    [b3[:, :, :npr], grid + npr, b3[:, :, npr + npz:]], -1)
            else:
                pos = jnp.broadcast_to(base[None], (3,) + base.shape)
            return x, pos, base, n_prefix
        return x, base, base, n_prefix

    # ---------------- public apply
    def apply(self, params: Params, batch: Dict[str, jnp.ndarray], *,
              mode: str = "train", cache: Optional[Params] = None,
              prompts: Optional[jnp.ndarray] = None, impl: str = "ref",
              remat: bool = False, dtype=jnp.float32) -> Dict[str, Any]:
        cfg = self.cfg
        out: Dict[str, Any] = {}

        # ----- encoder (whisper): frames -> encoder_out
        encoder_out = None
        if cfg.encoder is not None:
            if mode == "decode":
                encoder_out = cache["encoder_out"]
            else:
                frames = batch["frames"].astype(dtype)         # (B, F, D)
                Bf, F, _ = frames.shape
                fpos = jnp.broadcast_to(
                    jnp.arange(F, dtype=jnp.int32)[None], (Bf, F))
                h = frames + L.sinusoidal_embedding(
                    fpos, cfg.d_model).astype(dtype)
                ectx = L.Ctx(mode="train", positions=fpos, impl=impl,
                             remat=remat, causal=False)
                h, _, _ = self._run_stack(
                    params["encoder"]["cycle"], ("attn",), h, ectx, None)
                encoder_out = L.apply_norm(
                    params["encoder"]["final_norm"], h, cfg.norm)

        x, positions, seq_pos, n_prefix = self._embed(
            params, batch, mode, prompts, dtype)
        ctx = L.Ctx(mode=mode, positions=positions, seq_pos=seq_pos,
                    impl=impl, remat=remat, encoder_out=encoder_out,
                    causal=(cfg.arch_type != "vit"))

        new_cache = dict(cache) if cache is not None else None
        aux_total = jnp.float32(0.0)

        if cfg.n_dense_layers:
            c = new_cache.get("dense_stack") if new_cache else None
            x, aux, nc = self._run_stack(
                params["dense_stack"], ("attn",), x, ctx, c)
            aux_total += aux
            if new_cache is not None:
                new_cache["dense_stack"] = nc

        cyc_cache = new_cache["cycle"] if new_cache else None
        shared = params.get("shared_attn")
        stacked = params["cycle"].copy()
        if "shared_attn" in cfg.layer_pattern:
            # scanning needs an entry per position; shared weights come from
            # the closure, so feed an empty pytree at those positions.
            for i, kind in enumerate(cfg.layer_pattern):
                if kind == "shared_attn":
                    stacked[f"pos{i}"] = {"_": jnp.zeros((cfg.n_cycles,))}
        x, aux, nc = self._run_stack(
            stacked, cfg.layer_pattern, x, ctx, cyc_cache, shared=shared)
        aux_total += aux
        if new_cache is not None:
            new_cache["cycle"] = nc
            if cfg.encoder is not None and mode == "prefill":
                new_cache["encoder_out"] = encoder_out

        x = L.apply_norm(params["final_norm"], x, cfg.norm)

        if cfg.arch_type == "vit":
            logits = L.dense(params["head"], x[:, 0])          # cls token
            out.update(logits=logits, hidden=x, aux_loss=aux_total)
            return out

        out["hidden"] = x
        out["n_prefix"] = n_prefix
        head_w = (params["head"]["w"] if "head" in params
                  else params["embed"]["tok"].T)
        logits = x @ head_w.astype(x.dtype)
        if cfg.final_logit_softcap:
            c = cfg.final_logit_softcap
            logits = c * jnp.tanh(logits / c)
        out["logits"] = logits
        out["aux_loss"] = aux_total

        if cfg.mtp and mode == "train":
            # DeepSeek-V3 MTP: predict token t+2 from (h_t, emb(tok_{t+1}))
            toks = batch["tokens"]
            emb_next = jnp.take(params["embed"]["tok"].astype(x.dtype),
                                toks[:, 1:], axis=0)
            h_txt = x[:, n_prefix:, :]
            hcat = jnp.concatenate([h_txt[:, :-1], emb_next], -1)
            hm = L.dense(params["mtp"]["proj"], hcat)
            mpos = (positions[:, n_prefix:-1] if positions.ndim == 2
                    else positions[:, :, n_prefix:-1])
            mctx = L.Ctx(mode="train", positions=mpos, impl=impl)
            hm, _, _ = apply_block(params["mtp"]["block"], cfg, "attn", hm,
                                   mctx, None)
            hm = L.apply_norm(params["mtp"]["norm"], hm, cfg.norm)
            out["mtp_logits"] = hm @ head_w.astype(hm.dtype)

        if cache is not None:
            out["cache"] = new_cache
        return out
