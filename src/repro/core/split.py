"""Three-way model split W = [W_h | W_b | W_t] (SFPrompt Sec. 3.1).

The head (embedding frontend + the first layers) and the tail (last layers +
final norm + task head) live on the CLIENT; the body (everything between)
lives on the SERVER. Split points land on layer-pattern cycle boundaries so
every segment scans homogeneously. Per the paper the split is dynamic —
`SplitConfig.head_cycles/tail_cycles` choose it per deployment.

The head->body and body->tail cut points are real wire boundaries: a
`runtime.boundary.WireSpec` (default raw fp32) owns a codec per link, and
`forward(route="split")` pushes every smashed activation — and, via the
codec's custom VJP, every cut-layer gradient — through it, reporting the
measured bytes in `out["wire_bytes"]`. See ARCHITECTURE.md §Segment
pipeline.

Segment placement notes (DESIGN.md §Arch-applicability):
  - deepseek-v3: the 3 dense prefix layers belong to the head.
  - whisper: the (stubbed-frontend) encoder is client-side feature
    extraction, so it lives in the head segment.
  - zamba2: the shared attention block's weights are *replicated* into every
    segment that contains one of its sites; only the tail's copy is
    trainable, mirroring what a physical split forces.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.transformer import (init_block,
                                      init_stack, run_stack,
                                      stack_cache)
from repro.runtime.boundary import WireSpec

Params = Dict[str, Any]

# what a training body layer keeps for its backward pass (`body_fwd`)
BODY_TRAIN_SAVES = jax.checkpoint_policies.save_only_these_names(
    L.QKV, L.MLP_PRE)


@dataclass(frozen=True)
class SplitConfig:
    head_cycles: int = 1          # cycles of the layer pattern in W_h
    tail_cycles: int = 1          # cycles in W_t
    prompt_len: int = 16          # p — soft prompt tokens (VPT-style)
    prune_gamma: float = 0.5      # fraction of local data PRUNED away
    local_epochs: int = 10        # U — phase-1 self-update epochs
    capacity_note: str = ""


class SplitModel:
    def __init__(self, cfg: ModelConfig, split: SplitConfig,
                 wire: Optional[WireSpec] = None):
        if split.head_cycles + split.tail_cycles >= cfg.n_cycles:
            raise ValueError(
                f"{cfg.name}: head({split.head_cycles}) + tail"
                f"({split.tail_cycles}) cycles must leave a non-empty body"
                f" out of {cfg.n_cycles}")
        self.cfg = cfg
        self.split = split
        # The two physical links of the split; route="split" traffic always
        # crosses them (route="local" is client-only, zero wire traffic).
        self.wire = wire if wire is not None else WireSpec.make("fp32")
        self.body_cycles = cfg.n_cycles - split.head_cycles - split.tail_cycles
        cyc = len(cfg.layer_pattern)
        self.n_head_layers = cfg.n_dense_layers + split.head_cycles * cyc
        self.n_tail_layers = split.tail_cycles * cyc
        self.n_body_layers = self.body_cycles * cyc
        self._has_shared = "shared_attn" in cfg.layer_pattern

    # -------------------------------------------------------------- sizes
    def segment_fractions(self):
        """(alpha, tau) parameter fractions of |W| in head and body — feeds
        the Table-1 cost model."""
        total = self.cfg.param_count()
        h = self._segment_params_count("head")
        b = self._segment_params_count("body")
        return h / total, b / total

    def _segment_params_count(self, seg: str) -> int:
        import numpy as _np
        shapes = jax.eval_shape(lambda k: self.init(k)[seg],
                                jax.random.PRNGKey(0))
        return sum(int(_np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    # -------------------------------------------------------------- init
    def init(self, key) -> Params:
        cfg = self.cfg
        keys = iter(jax.random.split(key, 32))
        head: Params = {}
        # embedding frontend
        if cfg.arch_type == "vit":
            patch_dim = 16 * 16 * 3
            head["embed"] = {
                "patch": L.dense_init(next(keys), patch_dim, cfg.d_model),
                "cls": 0.02 * jax.random.normal(next(keys), (1, cfg.d_model)),
                "pos": 0.02 * jax.random.normal(
                    next(keys), (cfg.max_seq_len, cfg.d_model)),
            }
        else:
            head["embed"] = {"tok": 0.02 * jax.random.normal(
                next(keys), (cfg.vocab_size, cfg.d_model), jnp.float32)}
        if cfg.encoder is not None:
            head["encoder"] = {
                "cycle": {"pos0": init_stack(next(keys), cfg, "attn",
                                             cfg.encoder.n_layers)},
                "final_norm": L.norm_init(cfg.d_model, cfg.norm)}
        if cfg.n_dense_layers:
            head["dense_stack"] = {"pos0": init_stack(
                next(keys), cfg, "attn", cfg.n_dense_layers)}
        head["stack"] = self._init_cycles(next(keys), self.split.head_cycles)

        body: Params = {"stack": self._init_cycles(next(keys), self.body_cycles)}

        tail: Params = {"stack": self._init_cycles(next(keys),
                                                   self.split.tail_cycles)}
        tail["final_norm"] = L.norm_init(cfg.d_model, cfg.norm)
        out_dim = cfg.num_classes or cfg.vocab_size
        tail["head"] = L.dense_init(next(keys), cfg.d_model, out_dim)

        if self._has_shared:
            sh = init_block(next(keys), cfg, "shared_attn")
            for seg in (head, body, tail):
                seg["shared_attn"] = jax.tree.map(jnp.copy, sh)

        prompt = 0.02 * jax.random.normal(
            next(keys), (self.split.prompt_len, cfg.d_model), jnp.float32)
        return {"head": head, "body": body, "tail": tail, "prompt": prompt}

    def _init_cycles(self, key, n_cycles: int) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, len(cfg.layer_pattern))
        out = {}
        for i, kind in enumerate(cfg.layer_pattern):
            if kind == "shared_attn":
                out[f"pos{i}"] = {"_": jnp.zeros((n_cycles,))}
            else:
                out[f"pos{i}"] = init_stack(ks[i], cfg, kind, n_cycles)
        return out

    # -------------------------------------------------------------- caches
    def init_cache(self, batch: int, seq_len: int, dtype=jnp.float32,
                   window=None) -> Params:
        cfg = self.cfg

        def seg_cache(n_cycles):
            return {f"pos{i}": stack_cache(cfg, kind, n_cycles, batch,
                                           seq_len, dtype, window=window)
                    for i, kind in enumerate(cfg.layer_pattern)}

        cache: Params = {
            "head": {"stack": seg_cache(self.split.head_cycles)},
            "body": {"stack": seg_cache(self.body_cycles)},
            "tail": {"stack": seg_cache(self.split.tail_cycles)},
        }
        if cfg.n_dense_layers:
            cache["head"]["dense_stack"] = {"pos0": stack_cache(
                cfg, "attn", cfg.n_dense_layers, batch, seq_len, dtype,
                window=window)}
        if cfg.encoder is not None:
            cache["head"]["encoder_out"] = jnp.zeros(
                (batch, cfg.encoder.n_frames, cfg.d_model), dtype)
        return cache

    # ------------------------------------------------- slotted allocation
    # A serving engine's shared KV cache is `init_cache(n_slots, ...)`:
    # every batch row is a SLOT that one in-flight request owns. The
    # helpers below move whole slots between a fresh single-request cache
    # and the shared one, so a prefill computed at batch=1 can join an
    # in-flight decode batch without draining it (serve/engine.py).

    def blank_slot_cache(self, seq_len: int, dtype=jnp.float32,
                         window=None) -> Params:
        """A fresh batch=1 cache — the state of one unoccupied slot."""
        return self.init_cache(1, seq_len, dtype, window=window)

    @staticmethod
    def _slot_axis(path) -> int:
        # every cache leaf carries the batch (=slot) axis at 1, after the
        # stacked-layer axis — except the head's encoder_out at axis 0
        return 0 if any(getattr(p, "key", None) == "encoder_out"
                        for p in path) else 1

    def cache_write_slot(self, shared: Params, single: Params,
                         slot) -> Params:
        """Scatter a batch=1 cache pytree into slot `slot` (traced int) of
        the shared n-slot cache. Overwrites every leaf of that slot, so a
        newly allocated slot never sees a previous tenant's KV state."""
        def wr(path, s, one):
            ax = self._slot_axis(path)
            return jax.lax.dynamic_update_index_in_dim(
                s, jnp.take(one, 0, axis=ax).astype(s.dtype), slot, ax)
        return jax.tree_util.tree_map_with_path(wr, shared, single)

    def cache_read_slot(self, shared: Params, slot) -> Params:
        """Gather slot `slot` of the shared cache as a batch=1 cache."""
        def rd(path, s):
            ax = self._slot_axis(path)
            return jnp.expand_dims(
                jax.lax.dynamic_index_in_dim(s, slot, ax, keepdims=False),
                ax)
        return jax.tree_util.tree_map_with_path(rd, shared)

    def jit_slot_writer(self, *, donate: bool = True):
        """Jitted `cache_write_slot` for serving engines. With `donate` the
        SHARED cache argument of the scatter is donated, so a slot join
        updates the n-slot pytree in place instead of copying every cache
        leaf per admission (decode fast path — backends without donation
        silently fall back to the copy)."""
        return jax.jit(self.cache_write_slot,
                       donate_argnums=(0,) if donate else ())

    # ------------------------------------------------- paged allocation
    # The paged serving cache replaces the dense (slot, window) pair with a
    # PAGE POOL: every attention-cache leaf becomes (n_cycles, n_pages,
    # page_size, ...) and a per-slot BLOCK TABLE of physical page ids maps a
    # slot's logical blocks onto pool pages (serve/paged_engine.py owns the
    # host-side allocator). A pool is literally `init_cache(n_pages,
    # page_size)` — batch axis = page axis — so the page axis is uniformly
    # axis 1 of every leaf, after the stacked-layer axis.

    def paged_cache_unsupported(self) -> Optional[str]:
        """None when this model can serve from a paged pool; otherwise the
        reason it cannot. Paging assumes every cached layer is a uniform
        full-window attention cache (one ring layout shared by all leaves);
        recurrent state (mamba/rwkv), MLA latents, local-attention windows,
        encoder outputs and dense prefix stacks keep per-slot state the
        block tables cannot express yet."""
        cfg = self.cfg
        if cfg.arch_type in ("vit", "audio", "vlm"):
            return f"arch_type {cfg.arch_type!r} has no token decode loop"
        if any(kind != "attn" for kind in cfg.layer_pattern):
            return (f"layer pattern {cfg.layer_pattern} has non-'attn' "
                    f"layers")
        if cfg.attention is not None and cfg.attention.mla is not None:
            return "MLA latent caches are not paged yet"
        if cfg.n_dense_layers:
            return f"{cfg.n_dense_layers} dense prefix layers are not paged"
        if cfg.encoder is not None:
            return "encoder models have no token decode loop"
        return None

    def init_paged_cache(self, n_pages: int, page_size: int,
                         dtype=jnp.float32) -> Params:
        """The device-side page pool: one page axis shared by head, body
        and tail stacks (a page id is valid in every layer's pool)."""
        reason = self.paged_cache_unsupported()
        if reason is not None:
            raise ValueError(f"{self.cfg.name}: paged cache unsupported — "
                             f"{reason}")
        return self.init_cache(n_pages, page_size, dtype)

    @staticmethod
    def paged_seg_view(seg_cache: Params, tables) -> Params:
        """Inject the (S, n_blocks) block tables into every stacked layer
        group of one segment's pool (broadcast over the cycle axis so they
        ride the layer scan); `apply_attention` detects the key and takes
        the paged decode path."""
        stacks = {}
        for name, stack in seg_cache["stack"].items():
            n = stack["positions"].shape[0]
            stacks[name] = dict(stack, block_tables=jnp.broadcast_to(
                tables[None], (n,) + tables.shape))
        return {"stack": stacks}

    @staticmethod
    def strip_paged_view(seg_cache: Params) -> Params:
        """Drop the injected block tables, leaving the bare pool pytree."""
        return {"stack": {name: {k: v for k, v in stack.items()
                                if k != "block_tables"}
                          for name, stack in seg_cache["stack"].items()}}

    @staticmethod
    def paged_gather(pool: Params, tables) -> Params:
        """Gather per-slot dense cache views out of a pool: leaf
        (n, P, page, ...) + tables (S, nb) -> (n, S, nb*page, ...), laid out
        exactly like a dense `init_cache(S, nb*page)` slot cache (block j
        covers width indices [j*page, (j+1)*page))."""
        S, nb = tables.shape

        def g(leaf):
            out = leaf[:, tables]                    # (n, S, nb, page, ...)
            return out.reshape(leaf.shape[0], S, nb * leaf.shape[2],
                               *leaf.shape[3:])
        return jax.tree.map(g, pool)

    @staticmethod
    def paged_scatter_token(pool: Params, dense: Params, tables,
                            pos) -> Params:
        """Write back the single token each slot just wrote at width index
        `pos` (S,) of its dense view (the decode-step inverse of
        `paged_gather` — everything else in the dense view is unchanged
        pool content)."""
        S, nb = tables.shape
        s_idx = jnp.arange(S)

        def sc(pool_leaf, dense_leaf):
            page_len = pool_leaf.shape[2]
            page = tables[s_idx, pos // page_len]    # (S,)
            off = pos % page_len
            vals = dense_leaf[:, s_idx, pos]         # (n, S, ...)
            return pool_leaf.at[:, page, off].set(
                vals.astype(pool_leaf.dtype))
        return jax.tree.map(sc, pool, dense)

    @staticmethod
    def paged_scatter_slot(pool: Params, single: Params, table_row,
                           write_mask, scratch_page) -> Params:
        """Scatter one slot's batch=1 dense cache (width nb*page) into its
        pages. `write_mask` (nb,) bool selects the blocks to land; masked
        blocks (shared prefix pages, unallocated entries) are redirected to
        the scratch page so the op stays shape-stable without touching
        live pages."""
        nb = table_row.shape[0]
        dest = jnp.where(write_mask, table_row, scratch_page)

        def sc(pool_leaf, dense_leaf):
            page_len = pool_leaf.shape[2]
            r = dense_leaf[:, 0].reshape(dense_leaf.shape[0], nb, page_len,
                                         *dense_leaf.shape[3:])
            return pool_leaf.at[:, dest].set(r.astype(pool_leaf.dtype))
        return jax.tree.map(sc, pool, single)

    @staticmethod
    def paged_copy_page(pool: Params, src, dst) -> Params:
        """Copy one physical page across every layer's pool — the COW
        divergence copy for a shared boundary page."""
        return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), pool)

    # -------------------------------------------------------------- embed
    def _embed(self, head_p, batch, mode, prompt, dtype, chunk_start=None):
        cfg = self.cfg
        emb = head_p["embed"]
        if cfg.arch_type == "vit":
            patches = batch["patches"]
            B = patches.shape[0]
            x = L.dense(emb["patch"], patches.astype(dtype))
            cls = jnp.broadcast_to(emb["cls"][None], (B, 1, cfg.d_model))
            x = jnp.concatenate([cls.astype(x.dtype), x], 1)
            if prompt is not None:
                pr = jnp.broadcast_to(prompt[None], (B,) + prompt.shape)
                x = jnp.concatenate([x[:, :1], pr.astype(x.dtype), x[:, 1:]], 1)
            x = x + emb["pos"][: x.shape[1]].astype(x.dtype)
            pos = jnp.broadcast_to(
                jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
            return x, pos, pos, 0

        toks = batch["tokens"]
        B, S = toks.shape
        x = jnp.take(emb["tok"].astype(dtype), toks, axis=0)
        n_prefix = 0
        if cfg.arch_type == "vlm" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(dtype)
            x = jnp.concatenate([pe, x], axis=1)
            n_prefix += pe.shape[1]
        if prompt is not None and mode != "decode" and chunk_start is None:
            pr = jnp.broadcast_to(prompt[None], (B,) + prompt.shape)
            x = jnp.concatenate([pr.astype(dtype), x], axis=1)
            n_prefix += prompt.shape[0]

        T = x.shape[1]
        if mode == "decode":
            base = batch["pos"][:, None]
        elif chunk_start is not None:
            # chunked-prefill continuation: this chunk's tokens sit at
            # positions [chunk_start, chunk_start + T) of an already
            # partially-filled cache; no soft prompt is prepended (it went
            # in with the first chunk).
            base = chunk_start[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        else:
            base = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        base = base.astype(jnp.int32)
        if cfg.arch_type == "audio":
            # whisper decoder: absolute sinusoidal positions on the tokens,
            # at their sequence index past the prefix, as decode's `pos`
            x = x.at[:, T - S:].add(L.sinusoidal_embedding(
                base[:, T - S:], cfg.d_model).astype(dtype))
        att = cfg.attention
        if att is not None and att.mrope_sections is not None:
            # M-RoPE: layout is [prompt | patches | text]; patch grid
            # positions come from the frontend stub, offset past the prompts;
            # masking & cache slots always use the sequence index `base`.
            if mode != "decode" and "mrope_positions" in batch:
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

    # -------------------------------------------------------------- segments
    def _seg_fwd(self, seg_p, seg_name, n_cycles, x, ctx, cache,
                 policy=None):
        cfg = self.cfg
        caches = cache["stack"] if cache is not None else None
        x, aux, new_stack = run_stack(
            cfg, seg_p["stack"], cfg.layer_pattern, x, ctx, caches,
            shared=seg_p.get("shared_attn"), policy=policy)
        new_cache = None
        if cache is not None:
            new_cache = dict(cache)
            new_cache["stack"] = new_stack
        return x, aux, new_cache

    def head_fwd(self, head_p, prompt, batch, *, mode="train", cache=None,
                 impl="ref", dtype=jnp.float32, remat=False,
                 unroll=False, chunk_start=None) -> Dict[str, Any]:
        """Client-side: embed (+prompts, + whisper encoder) -> head layers.
        Output `smashed` is the cut-layer activation sent to the server.
        `chunk_start` (B,) marks a chunked-prefill continuation: the batch's
        tokens extend a partially-filled prefill cache starting at those
        positions (attention then runs write-then-attend over the full
        cache, like decode, instead of chunk-local causal)."""
        cfg = self.cfg
        encoder_out = None
        new_cache = dict(cache) if cache is not None else None
        if cfg.encoder is not None:
            if mode == "decode":
                encoder_out = cache["encoder_out"]
            else:
                frames = batch["frames"].astype(dtype)
                Bf, F, _ = frames.shape
                fpos = jnp.broadcast_to(
                    jnp.arange(F, dtype=jnp.int32)[None], (Bf, F))
                h = frames + L.sinusoidal_embedding(fpos, cfg.d_model).astype(dtype)
                ectx = L.Ctx(mode="train", positions=fpos, impl=impl,
                             causal=False, remat=remat, unroll=unroll)
                h, _, _ = run_stack(cfg, head_p["encoder"]["cycle"], ("attn",),
                                    h, ectx, None)
                encoder_out = L.apply_norm(
                    head_p["encoder"]["final_norm"], h, cfg.norm)
                if new_cache is not None:
                    new_cache["encoder_out"] = encoder_out

        x, positions, seq_pos, n_prefix = self._embed(
            head_p, batch, mode, prompt, dtype, chunk_start)
        ctx = L.Ctx(mode=mode, positions=positions, seq_pos=seq_pos,
                    impl=impl, remat=remat, unroll=unroll,
                    causal=(cfg.arch_type != "vit"), encoder_out=encoder_out,
                    has_context=(chunk_start is not None))
        aux = jnp.float32(0.0)
        if cfg.n_dense_layers:
            c = cache.get("dense_stack") if cache is not None else None
            x, a, nc = run_stack(cfg, head_p["dense_stack"], ("attn",), x,
                                 ctx, c)
            aux += a
            if new_cache is not None:
                new_cache["dense_stack"] = nc
        seg_cache = {"stack": cache["stack"]} if cache is not None else None
        x, a, nc = self._seg_fwd(head_p, "head", self.split.head_cycles, x,
                                 ctx, seg_cache)
        aux += a
        if new_cache is not None:
            new_cache["stack"] = nc["stack"]
        return {"smashed": x, "positions": positions, "seq_pos": seq_pos,
                "n_prefix": n_prefix, "encoder_out": encoder_out, "aux": aux,
                "cache": new_cache, "mode": mode, "impl": impl,
                "remat": remat, "unroll": unroll,
                "has_context": chunk_start is not None}

    def _ctx_from(self, head_out) -> L.Ctx:
        return L.Ctx(mode=head_out["mode"], positions=head_out["positions"],
                     seq_pos=head_out["seq_pos"], impl=head_out["impl"],
                     remat=head_out.get("remat", False),
                     unroll=head_out.get("unroll", False),
                     causal=(self.cfg.arch_type != "vit"),
                     encoder_out=head_out["encoder_out"],
                     has_context=head_out.get("has_context", False))

    def body_fwd(self, body_p, smashed, head_out, *, cache=None):
        """Server-side: frozen body over the smashed activations.

        In training the body is differentiated for its input only, so its
        layers keep just q/k/v and the MLP's pre-activation for the
        backward pass and recompute the rest there from them: norms,
        activations, the S x S scores and softmax, which would otherwise
        be stacked per layer, and the attention's output."""
        ctx = self._ctx_from(head_out)
        policy = (BODY_TRAIN_SAVES if ctx.mode == "train" and cache is None
                  else None)
        x, aux, new_cache = self._seg_fwd(
            body_p, "body", self.body_cycles, smashed, ctx, cache, policy)
        return {"smashed": x, "aux": aux, "cache": new_cache}

    def tail_fwd(self, tail_p, x, head_out, *, cache=None,
                 last_only: bool = False):
        """Client-side: tail layers -> final norm -> task head.
        last_only=True computes logits for the final position only — the
        production prefill semantics (avoids materializing/reducing the
        (B, S, V) logits tensor; see EXPERIMENTS.md §Perf pair A)."""
        cfg = self.cfg
        ctx = self._ctx_from(head_out)
        x, aux, new_cache = self._seg_fwd(
            tail_p, "tail", self.split.tail_cycles, x, ctx, cache)
        hidden = L.apply_norm(tail_p["final_norm"], x, cfg.norm)
        out: Dict[str, Any] = {"aux": aux, "cache": new_cache, "hidden": hidden}
        if cfg.arch_type == "vit":
            out["logits"] = L.dense(tail_p["head"], hidden[:, 0])
            return out
        if last_only:
            hidden = hidden[:, -1:, :]
        logits = hidden @ tail_p["head"]["w"].astype(hidden.dtype)
        if cfg.final_logit_softcap:
            c = cfg.final_logit_softcap
            logits = c * jnp.tanh(logits / c)
        out["logits"] = logits
        out["n_prefix"] = head_out["n_prefix"]
        return out

    # -------------------------------------------------------------- routes
    def forward(self, params, batch, *, route="split", mode="train",
                cache=None, impl="ref", dtype=jnp.float32, remat=False,
                unroll=False, prompt=None, last_only=True, wire_key=None,
                chunk_start=None):
        """route='split': head -> body -> tail (phase 2), every smashed
        tensor crossing the head_body / body_tail wire boundaries through
        their codecs; out['wire_bytes'] holds the measured bytes per link.
        route='local': head -> tail directly (phase 1 local-loss update and
        EL2N scoring — the body is skipped, zero server communication).
        `chunk_start` (B,) runs a chunked-prefill continuation (see
        `head_fwd`); the soft prompt went in with the first chunk, so none
        is prepended here."""
        if chunk_start is not None:
            prompt = None
        else:
            prompt = params["prompt"] if prompt is None else prompt
        hc = cache["head"] if cache is not None else None
        ho = self.head_fwd(params["head"], prompt, batch, mode=mode,
                           cache=hc, impl=impl, dtype=dtype, remat=remat,
                           unroll=unroll, chunk_start=chunk_start)
        x, aux = ho["smashed"], ho["aux"]
        new_cache = {"head": ho["cache"]} if cache is not None else None
        wire_bytes = {}
        train = mode == "train"
        if route == "split":
            k_hb = k_bt = None
            if wire_key is not None:
                k_hb, k_bt = jax.random.split(wire_key)
            x, wire_bytes["head_body"] = self.wire.head_body.transmit(
                x, key=k_hb, train=train)
            bo = self.body_fwd(params["body"], x, ho,
                               cache=cache["body"] if cache else None)
            x = bo["smashed"]
            aux += bo["aux"]
            if cache is not None:
                new_cache["body"] = bo["cache"]
            x, wire_bytes["body_tail"] = self.wire.body_tail.transmit(
                x, key=k_bt, train=train)
        to = self.tail_fwd(params["tail"], x, ho,
                           cache=cache["tail"] if cache else None,
                           last_only=(mode == "prefill" and last_only))
        out = dict(to)
        out["aux"] = aux + to["aux"]
        out["wire_bytes"] = wire_bytes
        if cache is not None:
            new_cache["tail"] = to["cache"]
            out["cache"] = new_cache
        return out
