"""Memory-bandwidth utilisation of split-serving decode: the least bytes
the window's decode iterations had to read, over the traced window times
the chip's HBM bandwidth, in percent.

One decode iteration computes one token for every active slot. The least
it must read from HBM, in float32 as the weights and the cache are
served:

- every layer's weights once (head, body and tail: attention with its
  q/k/v biases, SwiGLU MLP, two RMSNorm scales), whatever the number of
  slots, since one pass over a weight serves them all;
- the final RMSNorm and the output layer once per distinct tenant among
  the active slots, since each tenant has its own;
- the embedding rows of the active slots' input tokens;
- K and V of every position each active slot's cache holds, in every
  layer.

Activations, the logits and the new token's K/V are written, and are a
few MB against the ~6.4 GB above; they are left out, as are prefills.
Every counted byte is one the iteration cannot avoid reading, so the
share cannot pass 100% unless the time leaves out part of the work.
"""

BYTES = 4   # float32 weights and cache


def layer_values(cfg) -> int:
    """Values of one decoder layer: q, k, v (with biases) and o, the
    SwiGLU MLP, and the two RMSNorm scales."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return D * q + q + 2 * (D * kv + kv) + q * D + 3 * D * F + 2 * D


def iteration_bytes(cfg, active: int, tenants: int, positions: int) -> int:
    """The least bytes of one decode iteration with `active` slots of
    `tenants` distinct tenants whose caches hold `positions` in all."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    L = cfg["num_hidden_layers"]
    return BYTES * (L * layer_values(cfg) + tenants * (D + D * V)
                    + active * D + positions * L * 2 * kv)


def read(ctx):
    t, its = ctx.get("trace"), ctx["window"].get("iterations")
    if not t or not its or t["window_s"] <= 0:
        return None
    need = sum(iteration_bytes(ctx["config"], a, n, p) for a, n, p in its)
    return 100.0 * need / (t["window_s"] * ctx["peaks"]["hbm_bytes_per_s"])
