"""Roofline share of the secure-aggregation kernel: the least time for its
input and output bytes over HBM bandwidth, over the device time of
`sfprompt_secure_masked_encode`, in percent. Each client of a round's
cohort encodes its trainable values (tail layers, final norm, classifier
and soft prompt): float32 in, uint32 ring words out."""

KERNEL = "sfprompt_secure_masked_encode"


def trainable_values(cfg) -> int:
    """Values a client uploads: what its tail and soft prompt hold."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    sp = cfg["split"]
    layer = 4 * D * D + 2 * D * F + 4 * D      # q, k, v, o, MLP, 2 norms
    return (sp["tail_layers"] * layer + 2 * D + D * cfg["num_labels"]
            + sp["prompt_len"] * D)


def read(ctx):
    t, cohorts = ctx.get("trace"), ctx["window"].get("cohorts")
    if not t or not cohorts or not ctx["traffic"].get("secure"):
        return None
    spent = t["op_s"].get(KERNEL, 0.0)
    if spent <= 0:
        return None
    need = sum(len(c) for c in cohorts) * 8.0 * trainable_values(ctx["config"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / spent
