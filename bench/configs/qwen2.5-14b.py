"""Qwen2.5-14B for the benchmark: weights from a seed, the layout the
program serves them in, and the plain reference forward pass.

The reference is straightforward jax.numpy with no kernels, caches,
paging or batching, and imports nothing of the program. It runs one
request at a time: the tenant's soft prompt in front of the prompt and
the served tokens, the head layer, the body layer and the tail layer
(each a pre-norm decoder layer with full causal attention), the final
RMSNorm and the output layer. `dtype` is the type it computes in: float32
for the reference, bfloat16 for the control; `precision` is that of every
matmul (`jax.lax.Precision`, None for JAX's default). RMSNorm statistics
and the softmax are taken in float32 either way.

The weights are made once on the device, in one jitted call, in a layout
whose leaves are the program's own arrays: `to_program` only regroups
them, so the engine and the reference read the same buffers and the 9.5
GB of weights exist once.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
LAYER_KEYS = ("attn_norm", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "mlp_norm", "w_gate", "w_up", "w_down")


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"D": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
            "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "prompt": cfg["split"]["prompt_len"],
            "tenants": cfg["serve"]["tenants"]}


# ------------------------------------------------------------------ weights
def _layers(keys, n: int, s: Dict[str, int], lead=()) -> Dict[str, Any]:
    """`n` stacked decoder layers (with leading axes `lead`)."""
    D, F, q, kv = s["D"], s["F"], s["H"] * s["Dh"], s["Hkv"] * s["Dh"]
    shapes = {"attn_norm": (D,), "wq": (D, q), "bq": (q,), "wk": (D, kv),
              "bk": (kv,), "wv": (D, kv), "bv": (kv,), "wo": (q, D),
              "mlp_norm": (D,), "w_gate": (D, F), "w_up": (D, F),
              "w_down": (F, D)}
    out = {}
    for name in LAYER_KEYS:
        shape = lead + (n,) + shapes[name]
        z = jax.random.normal(next(keys), shape, F32)
        out[name] = 1.0 + 0.1 * z if name.endswith("norm") else 0.02 * z
    return out


def make_weights(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """Canonical weights from a PRNG key. Traceable, so that one jitted
    call makes them on the device. Per-tenant leaves carry a leading
    tenant axis, as the program's tenant bank stacks them."""
    s = sizes(cfg)
    sp = cfg["split"]
    T, D, V = s["tenants"], s["D"], s["V"]
    keys = iter(jax.random.split(key, 64))
    mat = lambda *shape: 0.02 * jax.random.normal(next(keys), shape, F32)
    return {
        "embed": mat(V, D),
        "head": _layers(keys, sp["head_layers"], s),
        "body": _layers(keys, sp["body_layers"], s),
        "tails": {"layers": _layers(keys, sp["tail_layers"], s, lead=(T,)),
                  "final_norm": 1.0 + 0.1 * jax.random.normal(
                      next(keys), (T, D), F32),
                  "lm_head": mat(T, D, V)},
        "prompts": mat(T, s["prompt"], D),
    }


def _program_layer(l: Dict[str, Any]) -> Dict[str, Any]:
    return {"pos0": {
        "attn": {"ln": {"scale": l["attn_norm"]},
                 "q": {"w": l["wq"], "b": l["bq"]},
                 "k": {"w": l["wk"], "b": l["bk"]},
                 "v": {"w": l["wv"], "b": l["bv"]},
                 "o": {"w": l["wo"]}},
        "mlp": {"ln": {"scale": l["mlp_norm"]}, "gate": {"w": l["w_gate"]},
                "up": {"w": l["w_up"]}, "down": {"w": l["w_down"]}}}}


def to_program(w: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The same arrays in the program's pytrees: the shared head and body
    (`SplitModel.init`'s `head` and `body`), and the tenant bank's stacked
    tails and soft prompts. Regrouping only: no array is copied."""
    t = w["tails"]
    return {
        "shared": {"head": {"embed": {"tok": w["embed"]},
                            "stack": _program_layer(w["head"])},
                   "body": {"stack": _program_layer(w["body"])}},
        "tails": {"stack": _program_layer(t["layers"]),
                  "final_norm": {"scale": t["final_norm"]},
                  "head": {"w": t["lm_head"]}},
        "prompts": w["prompts"],
    }


# ------------------------------------------------------------------ forward
def rms_norm(x, scale, eps):
    xf = x.astype(F32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope(x, positions, theta):
    """Rotate-half RoPE over the last axis of x (T, H, Dh)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = positions.astype(F32)[:, None] * inv[None, :]       # (T, Dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2].astype(F32), x[..., dh // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def layer(p, x, cfg, dtype, precision=None):
    """One pre-norm decoder layer over a whole sequence x (T, D), causal;
    `p` holds one layer's weights."""
    T = x.shape[0]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    mm = lambda a, name: jnp.matmul(a, p[name].astype(dtype),
                                    precision=precision)
    pos = jnp.arange(T)
    h = rms_norm(x, p["attn_norm"], eps)
    q = (mm(h, "wq") + p["bq"].astype(dtype)).reshape(T, H, Dh)
    k = (mm(h, "wk") + p["bk"].astype(dtype)).reshape(T, Hkv, Dh)
    v = (mm(h, "wv") + p["bv"].astype(dtype)).reshape(T, Hkv, Dh)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=1)          # query head h: kv h // G
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=precision).astype(F32) / math.sqrt(Dh)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    # softmax(s) v, normalised after the weighted sum, as the program's
    # attention kernels do: at one bfloat16 pass the place of the division
    # decides which weights are rounded for the product
    e = jnp.exp(s - s.max(-1, keepdims=True))
    o = jnp.einsum("hqk,khd->qhd", e.astype(dtype), v, precision=precision)
    o = (o.astype(F32) / e.sum(-1).T[:, :, None]).astype(dtype)
    o = o.reshape(T, H * Dh)
    x = x + mm(o, "wo")
    h = rms_norm(x, p["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(h, "w_gate")) * mm(h, "w_up"), "w_down")


def run_layers(stacked, x, cfg, dtype, precision=None):
    for i in range(stacked["wq"].shape[0]):
        x = layer({k: v[i] for k, v in stacked.items()}, x, cfg, dtype,
                  precision)
    return x


def forward(w, tokens, cfg, dtype=F32, tenant: int = 0, precision=None):
    """Logits (prompt_len + len(tokens), V) of one request: the tenant's
    soft prompt, then `tokens` (the prompt and the tokens served after
    it), through head, body and tail. Padding appended to `tokens` changes
    no earlier position's logits (causal)."""
    t = w["tails"]
    x = jnp.concatenate([w["prompts"][tenant].astype(dtype),
                         w["embed"][tokens].astype(dtype)], 0)
    x = run_layers(w["head"], x, cfg, dtype, precision)
    x = run_layers(w["body"], x, cfg, dtype, precision)
    x = run_layers({k: v[tenant] for k, v in t["layers"].items()}, x, cfg,
                   dtype, precision)
    x = rms_norm(x, t["final_norm"][tenant], cfg["rms_norm_eps"])
    return jnp.matmul(x, t["lm_head"][tenant].astype(dtype),
                      precision=precision).astype(F32)
