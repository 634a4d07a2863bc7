"""Model FLOP utilisation of split-serving decode: the model FLOPs of the
window's decode iterations over the traced window times the chip's bf16
peak, in percent. (The float32 dots run as bfloat16 MXU passes at the
default precision, so bf16 is the peak they can reach.)

Per active slot and iteration, a matmul of a (1, k) row by (k, n) is
2*k*n: q, k, v, o and the SwiGLU MLP of every layer, and the output
layer; attention is 2 * heads * head_dim for the scores and as much for
the values, per position its cache holds, in every layer. Idle slots that
the step computes for shape's sake, and prefills, do not count.
"""
from bench import common

MBU = common.load_module(common.BENCH / "metrics" / "mbu.decode.py")


def iteration_flops(cfg, active: int, positions: int) -> float:
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    # a layer's matmul weights: its values less biases and norm scales
    matmul = MBU.layer_values(cfg) - q - 2 * kv - 2 * D
    return 2.0 * active * (L * matmul + D * V) + 4.0 * q * L * positions


def read(ctx):
    t, its = ctx.get("trace"), ctx["window"].get("iterations")
    if not t or not its or t["window_s"] <= 0:
        return None
    flops = sum(iteration_flops(ctx["config"], a, p) for a, _, p in its)
    return 100.0 * flops / (t["window_s"]
                            * ctx["peaks"]["bf16_flops_per_s"])
