"""Model FLOP utilisation of the federated rounds: the model FLOPs of the
rounds completed in the traced window over the window times the chip's
bf16 peak, in percent.

Model FLOPs count what an SFPrompt round needs, from shapes alone; a
forward pass the program recomputes does not count. A matmul of (m, k) by
(k, n) is 2*m*k*n. Per client of a round's cohort: phase 1, local_epochs
passes over its rows through the head and the tail, forward and backward;
phase 1b, one EL2N scoring forward over its rows; phase 2, the kept rows
through head, body and tail, forward and backward. A layer whose weights
train costs three forwards (forward, input gradient, weight gradient); a
frozen layer the gradient passes through on its way to the soft prompt
costs two; the patch embedding sits below the prompt and costs one.
"""
from bench.systems.federated import keep_count


def layer_forward(T: int, D: int, F: int) -> float:
    """One encoder layer over one sequence of T tokens: q, k, v, o
    projections, the two attention products, and the MLP."""
    return 2.0 * T * (4 * D * D + 2 * D * F) + 4.0 * T * T * D


def client_flops(cfg, mix) -> float:
    """Model FLOPs of one client's part of a round."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    sp = cfg["split"]
    N = (cfg["image_size"] // cfg["patch_size"]) ** 2
    P = cfg["patch_size"] ** 2 * cfg["num_channels"]
    T = 1 + sp["prompt_len"] + N
    rows = mix["samples_per_client"]
    keep = keep_count(rows, sp["prune_gamma"], mix["batch"])
    layer = layer_forward(T, D, F)
    embed = 2.0 * N * P * D
    classify = 2.0 * D * cfg["num_labels"]
    head, body, tail = sp["head_layers"], sp["body_layers"], sp["tail_layers"]
    local_fwd = embed + (head + tail) * layer + classify
    local_train = embed + 2 * head * layer + 3 * (tail * layer + classify)
    split_train = (embed + 2 * (head + body) * layer
                   + 3 * (tail * layer + classify))
    return (mix["local_epochs"] * rows * local_train + rows * local_fwd
            + keep * split_train)


def read(ctx):
    t, cohorts = ctx.get("trace"), ctx["window"].get("cohorts")
    if not t or not cohorts:
        return None
    flops = sum(len(c) for c in cohorts) * client_flops(ctx["config"],
                                                        ctx["traffic"])
    return 100.0 * flops / (t["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
