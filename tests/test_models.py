"""Per-arch tests (assignment requirement): a REDUCED same-family variant of
each of the 10 assigned architectures runs through `SplitModel` — the one
model assembly — on CPU: forward on both routes with correct output shapes
and no NaNs, one train step, and decode against the KV cache matching the
full-sequence forward, soft prompt included."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config
from repro.core import losses
from repro.core.split import SplitConfig, SplitModel
from repro.optim import apply_updates, sgd

KEY = jax.random.PRNGKey(0)
SPLIT = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=4,
                    prune_gamma=0.5, local_epochs=2)
P = SPLIT.prompt_len


def build(arch):
    cfg = get_config(arch).reduced(n_layers=4)
    # reduced() keeps >= 1 cycle; ensure enough cycles for a 1/1/≥1 split
    if cfg.n_cycles < 3:
        cyc = len(cfg.layer_pattern)
        cfg = dataclasses.replace(
            cfg, n_layers=cfg.n_dense_layers + 3 * cyc)
    return cfg, SplitModel(cfg, SPLIT)


def make_batch(cfg, B=2, S=24, with_labels=False, seed=0):
    k = jax.random.PRNGKey(seed)
    if cfg.arch_type == "vit":
        b = {"patches": 0.1 * jax.random.normal(k, (B, 16, 16 * 16 * 3))}
        if with_labels:
            b["labels"] = jax.random.randint(k, (B,), 0, cfg.num_classes)
        return b
    b = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
    if cfg.arch_type == "vlm":
        b["patch_embeds"] = 0.02 * jax.random.normal(k, (B, 8, cfg.d_model))
        grid = jnp.stack(jnp.meshgrid(jnp.arange(2), jnp.arange(2),
                                      jnp.arange(2), indexing="ij"))
        b["mrope_positions"] = jnp.broadcast_to(
            grid.reshape(3, 8)[None], (B, 3, 8)).astype(jnp.int32)
    if cfg.arch_type == "audio":
        b["frames"] = 0.02 * jax.random.normal(
            k, (B, cfg.encoder.n_frames, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("route", ["split", "local"])
def test_reduced_smoke_forward(route, arch):
    cfg, model = build(arch)
    params = model.init(KEY)
    batch = make_batch(cfg, with_labels=True)
    out = model.forward(params, batch, route=route, mode="train")
    assert out["logits"].shape[0] == 2
    assert out["logits"].shape[-1] == (cfg.num_classes or cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(out["logits"])))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_reduced_smoke_train_step(arch):
    """One SGD step decreases (or at least computes) the LM/classifier loss
    with finite grads."""
    cfg, model = build(arch)
    params = model.init(KEY)
    batch = make_batch(cfg, with_labels=True)
    opt = sgd(1e-2)

    def loss_fn(p):
        out = model.forward(p, batch, route="split", mode="train")
        loss, _ = losses.task_loss(cfg, out, batch, impl="ref")
        return loss

    loss0, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss0))
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0
    updates, _ = opt.update(grads, opt.init(params), params)
    loss1 = loss_fn(apply_updates(params, updates))
    assert bool(jnp.isfinite(loss1))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_decode_matches_full(arch):
    """Prefill + one decode step == the full forward's last position. The
    soft prompt is prepended, so decode's `pos` counts it (whisper-base's
    sinusoidal positions must count it the same way in both)."""
    cfg, model = build(arch)
    params = model.init(KEY)
    B, S = 2, 16
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    bf = {"tokens": toks}
    bp = {"tokens": toks[:, :S]}
    extra = 0
    if cfg.arch_type == "vlm":
        pe = 0.02 * jax.random.normal(KEY, (B, 8, cfg.d_model))
        bf["patch_embeds"] = pe
        bp["patch_embeds"] = pe
        extra = 8
    if cfg.arch_type == "audio":
        fr = 0.02 * jax.random.normal(KEY, (B, cfg.encoder.n_frames,
                                            cfg.d_model))
        bf["frames"] = fr
        bp["frames"] = fr
    full = model.forward(params, bf, route="split", mode="train")["logits"]
    cache = model.init_cache(B, seq_len=64)
    pre = model.forward(params, bp, route="split", mode="prefill",
                        cache=cache)
    pos = jnp.full((B,), S + P + extra, jnp.int32)
    dec = model.forward(params, {"tokens": toks[:, S:S + 1], "pos": pos},
                        route="split", mode="decode", cache=pre["cache"])
    np.testing.assert_allclose(
        np.asarray(dec["logits"][:, 0]), np.asarray(full[:, -1]),
        rtol=2e-4, atol=2e-4)


def test_ring_buffer_decode_matches_full_window():
    """gemma2 sliding-window decode with a ring-buffer cache smaller than
    the sequence == full-cache decode (the window hides the difference)."""
    cfg, model = build("gemma2-9b")
    params = model.init(KEY)
    B, S = 1, 40
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    full_cache = model.init_cache(B, seq_len=P + S + 1)
    ring_cache = model.init_cache(B, seq_len=P + S + 1,
                                  window=cfg.attention.sliding_window)
    outs = []
    for cache in (full_cache, ring_cache):
        pre = model.forward(params, {"tokens": toks[:, :S]}, route="split",
                            mode="prefill", cache=cache)
        dec = model.forward(params, {"tokens": toks[:, S:S + 1],
                                     "pos": jnp.full((B,), P + S, jnp.int32)},
                            route="split", mode="decode", cache=pre["cache"])
        outs.append(np.asarray(dec["logits"][:, 0]))
    # local layers see identical windows; global layers differ only beyond
    # the ring window — with P + S < window they are identical too
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)


def test_multi_step_decode_consistency():
    """Greedy 4-step decode == teacher-forced full forward (stablelm)."""
    cfg, model = build("stablelm-12b")
    params = model.init(KEY)
    B, S, n_new = 1, 12, 4
    toks = jax.random.randint(KEY, (B, S + n_new), 0, cfg.vocab_size)
    full = model.forward(params, {"tokens": toks}, route="split",
                         mode="train")["logits"]
    cache = model.init_cache(B, seq_len=P + S + n_new)
    pre = model.forward(params, {"tokens": toks[:, :S]}, route="split",
                        mode="prefill", cache=cache)
    cache = pre["cache"]
    for i in range(n_new):
        pos = jnp.full((B,), P + S + i, jnp.int32)
        out = model.forward(params, {"tokens": toks[:, S + i:S + i + 1],
                                     "pos": pos},
                            route="split", mode="decode", cache=cache)
        cache = out["cache"]
        np.testing.assert_allclose(np.asarray(out["logits"][:, 0]),
                                   np.asarray(full[:, P + S + i]),
                                   rtol=2e-4, atol=2e-4)


def test_zamba2_weight_sharing():
    """The shared attention block is one parameter set used at every site
    of a segment: each segment holds its own copy, and the stack positions
    of that kind carry no per-layer weights."""
    cfg, model = build("zamba2-2.7b")
    params = model.init(KEY)
    for seg in ("head", "body", "tail"):
        assert "shared_attn" in params[seg]
        for i, kind in enumerate(cfg.layer_pattern):
            if kind == "shared_attn":
                assert set(params[seg]["stack"][f"pos{i}"]) == {"_"}


def test_param_count_sane():
    """Analytic param_count is within 2% of the initialized split model's
    count for every full-size assigned config (drives the Table-1 cost
    model), less what the split itself adds to the model."""
    def count(tree):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))

    for arch in ASSIGNED:
        cfg = get_config(arch)
        shapes = jax.eval_shape(SplitModel(cfg, SPLIT).init, KEY)
        actual = count(shapes)
        actual -= count(shapes["prompt"])              # the soft prompt
        if "shared_attn" in cfg.layer_pattern:
            # one copy of the shared block in each of head, body and tail
            actual -= 2 * count(shapes["tail"]["shared_attn"])
        if cfg.tie_embeddings:
            # the tail holds its own output layer; the tied model has none
            actual -= count(shapes["tail"]["head"])
        analytic = cfg.param_count()
        assert abs(actual - analytic) / actual < 0.02, \
            (arch, actual, analytic)
