"""ViT-B/16 for the benchmark: weights and data from a seed, the layout the
program takes them in, and the plain reference forward pass.

The reference is straightforward jax.numpy with no kernels, caches or
batching tricks, and imports nothing of the program. `dtype` is the type
the forward pass computes in: float32 for the reference (run under
'highest' matmul precision by the caller), bfloat16 for the control.
LayerNorm statistics and the softmax are taken in float32 either way.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    n_patches = (cfg["image_size"] // cfg["patch_size"]) ** 2
    return {"D": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
            "H": cfg["num_attention_heads"], "Dh": cfg["head_dim"],
            "F": cfg["intermediate_size"], "C": cfg["num_labels"],
            "P": cfg["patch_size"] ** 2 * cfg["num_channels"],
            "N": n_patches, "T": 1 + cfg["split"]["prompt_len"] + n_patches,
            "prompt": cfg["split"]["prompt_len"]}


# ------------------------------------------------------------------ weights
def make_weights(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """Canonical weights from a PRNG key: layers stacked on a leading axis.
    Traceable, so that one jitted call makes them on the device."""
    s = sizes(cfg)
    D, L, F = s["D"], s["L"], s["F"]
    keys = iter(jax.random.split(key, 32))
    mat = lambda *shape: 0.02 * jax.random.normal(next(keys), shape, F32)
    scale = lambda *shape: 1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                         F32)
    return {
        "patch_w": mat(s["P"], D), "cls": mat(1, D), "pos": mat(s["T"], D),
        "prompt": mat(s["prompt"], D),
        "layers": {"ln1_s": scale(L, D), "ln1_b": mat(L, D),
                   "wq": mat(L, D, D), "wk": mat(L, D, D),
                   "wv": mat(L, D, D), "wo": mat(L, D, D),
                   "ln2_s": scale(L, D), "ln2_b": mat(L, D),
                   "w_up": mat(L, D, F), "w_down": mat(L, F, D)},
        "final_s": scale(D), "final_b": mat(D), "head_w": mat(D, s["C"]),
    }


def make_data(cfg: Dict[str, Any], key, n_samples: int):
    """(patches (n, N, P), labels (n,)): a class anchor plus unit noise."""
    s = sizes(cfg)
    k_anchor, k_label, k_noise = jax.random.split(key, 3)
    anchors = jax.random.normal(k_anchor, (s["C"], s["N"], s["P"]), F32)
    labels = jax.random.randint(k_label, (n_samples,), 0, s["C"])
    noise = jax.random.normal(k_noise, (n_samples, s["N"], s["P"]), F32)
    return anchors[labels] + noise, labels.astype(jnp.int32)


def to_program(w: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The same weights in `SplitModel`'s pytree: head, body and tail each
    a stacked layer group `stack.pos0`."""
    sp = cfg["split"]
    h, b = sp["head_layers"], sp["head_layers"] + sp["body_layers"]
    lay = w["layers"]

    def group(lo, hi):
        g = lambda name: lay[name][lo:hi]
        return {"pos0": {
            "attn": {"ln": {"scale": g("ln1_s"), "bias": g("ln1_b")},
                     "q": {"w": g("wq")}, "k": {"w": g("wk")},
                     "v": {"w": g("wv")}, "o": {"w": g("wo")}},
            "mlp": {"ln": {"scale": g("ln2_s"), "bias": g("ln2_b")},
                    "up": {"w": g("w_up")}, "down": {"w": g("w_down")}}}}

    n = lay["wq"].shape[0]
    return {
        "head": {"embed": {"patch": {"w": w["patch_w"]}, "cls": w["cls"],
                           "pos": w["pos"]},
                 "stack": group(0, h)},
        "body": {"stack": group(h, b)},
        "tail": {"stack": group(b, n),
                 "final_norm": {"scale": w["final_s"], "bias": w["final_b"]},
                 "head": {"w": w["head_w"]}},
        "prompt": w["prompt"],
    }


def split(w: Dict[str, Any], cfg: Dict[str, Any]):
    """(frozen, trainable): the client head and server body stay frozen;
    the tail and the soft prompt train."""
    sp = cfg["split"]
    h, b = sp["head_layers"], sp["head_layers"] + sp["body_layers"]
    lay = w["layers"]
    frozen = {"patch_w": w["patch_w"], "cls": w["cls"], "pos": w["pos"],
              "head": jax.tree.map(lambda x: x[:h], lay),
              "body": jax.tree.map(lambda x: x[h:b], lay)}
    trainable = {"tail": jax.tree.map(lambda x: x[b:], lay),
                 "final_s": w["final_s"], "final_b": w["final_b"],
                 "head_w": w["head_w"], "prompt": w["prompt"]}
    return frozen, trainable


def program_trainable(tr: Dict[str, Any]) -> Dict[str, Any]:
    """A reference trainable tree in the layout of the program's
    (tail, prompt): used to line leaves up for comparison."""
    lay = tr["tail"]
    tail = {"stack": {"pos0": {
        "attn": {"ln": {"scale": lay["ln1_s"], "bias": lay["ln1_b"]},
                 "q": {"w": lay["wq"]}, "k": {"w": lay["wk"]},
                 "v": {"w": lay["wv"]}, "o": {"w": lay["wo"]}},
        "mlp": {"ln": {"scale": lay["ln2_s"], "bias": lay["ln2_b"]},
                "up": {"w": lay["w_up"]}, "down": {"w": lay["w_down"]}}}},
        "final_norm": {"scale": tr["final_s"], "bias": tr["final_b"]},
        "head": {"w": tr["head_w"]}}
    return {"tail": tail, "prompt": tr["prompt"]}


# ------------------------------------------------------------------ forward
def layer_norm(x, s, b, eps):
    xf = x.astype(F32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) / jnp.sqrt(var + eps) * s + b).astype(x.dtype)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def block(p, x, cfg, dtype):
    """One pre-norm encoder layer; `p` holds one layer's weights."""
    B, T, D = x.shape
    H, Dh = cfg["num_attention_heads"], cfg["head_dim"]
    eps = cfg["layer_norm_eps"]
    mm = lambda a, w: a @ w.astype(dtype)
    h = layer_norm(x, p["ln1_s"], p["ln1_b"], eps)
    q = mm(h, p["wq"]).reshape(B, T, H, Dh)
    k = mm(h, p["wk"]).reshape(B, T, H, Dh)
    v = mm(h, p["wv"]).reshape(B, T, H, Dh)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(F32) / math.sqrt(Dh)
    att = jax.nn.softmax(att, axis=-1).astype(dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, T, H * Dh)
    x = x + mm(o, p["wo"])
    h = layer_norm(x, p["ln2_s"], p["ln2_b"], eps)
    return x + mm(gelu_tanh(mm(h, p["w_up"])), p["w_down"])


def run_layers(stacked, x, cfg, dtype):
    for i in range(stacked["wq"].shape[0]):
        x = block(jax.tree.map(lambda a: a[i], stacked), x, cfg, dtype)
    return x


def head_forward(frozen, prompt, patches, cfg, dtype):
    """Client head: patch embedding, [CLS | prompt | patches] + positions,
    then the head layers."""
    B = patches.shape[0]
    x = patches.astype(dtype) @ frozen["patch_w"].astype(dtype)
    cls = jnp.broadcast_to(frozen["cls"].astype(dtype)[None],
                           (B, 1, x.shape[-1]))
    pr = jnp.broadcast_to(prompt.astype(dtype)[None],
                          (B,) + prompt.shape)
    x = jnp.concatenate([cls, pr, x], axis=1)
    x = x + frozen["pos"][: x.shape[1]].astype(dtype)
    return run_layers(frozen["head"], x, cfg, dtype)


def body_forward(frozen, x, cfg, dtype):
    return run_layers(frozen["body"], x, cfg, dtype)


def tail_forward(tr, x, cfg, dtype):
    """Client tail: the tail layers, final LayerNorm, classifier on CLS."""
    x = run_layers(tr["tail"], x, cfg, dtype)
    x = layer_norm(x, tr["final_s"], tr["final_b"], cfg["layer_norm_eps"])
    return (x[:, 0] @ tr["head_w"].astype(dtype)).astype(F32)
