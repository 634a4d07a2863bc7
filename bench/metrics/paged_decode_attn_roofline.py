"""Roofline share of the paged decode attention kernel: the least time
for its bytes at HBM bandwidth, over the device time of
`sfprompt_paged_decode_attention` in the traced window, in percent.

A call attends one query token per active slot over that slot's cache in
one layer's page pool. The least it must move, in float32: K and V of
every position the slot's cache holds, the grouped query heads in and
the attention output out. The kernel is bound by bytes (a few FLOPs per
byte). Calls per iteration (one per layer that attends through the page
pool) are taken from the trace: the kernel's calls over the window's
decode iterations.
"""

KERNEL = "sfprompt_paged_decode_attention"
BYTES = 4


def call_bytes(cfg, active: int, positions: int) -> int:
    """One call's least bytes for `active` slots whose caches hold
    `positions` in all."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return BYTES * (positions * 2 * kv + active * 2 * q)


def read(ctx):
    t, its = ctx.get("trace"), ctx["window"].get("iterations")
    if not t or not its:
        return None
    spent, calls = t["op_s"].get(KERNEL, 0.0), t["op_n"].get(KERNEL, 0)
    if spent <= 0 or calls <= 0:
        return None
    one_layer = sum(call_bytes(ctx["config"], a, p) for a, _, p in its)
    need = one_layer * calls / len(its)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / spent
