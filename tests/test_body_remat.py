"""The frozen body's backward pass in phase 2: what its layers keep, that
the gradients are those of the plain forward, and that serving is left
without a checkpoint."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ProtocolConfig, SFPromptTrainer, SplitConfig, SplitModel
from repro.models.transformer import apply_block
from repro.serve.steps import (make_paged_multi_decode_step,
                               make_tenant_prefill_step)

KEY = jax.random.PRNGKey(0)
B = 2            # examples in a batch; 3 body layers, so no shape clash
SPLIT = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=4)


def _vit():
    cfg = get_config("vit-base").reduced(n_layers=5, d_model=64, d_ff=128)
    batch = {"patches": jax.random.normal(KEY, (B, 4, 768)),
             "labels": jnp.array([1, 7], jnp.int32)}
    return cfg, batch


def _causal_lm():
    cfg = get_config("qwen2.5-14b").reduced(
        n_layers=5, d_model=64, d_ff=128, vocab_size=128)
    return cfg, {"tokens": jax.random.randint(KEY, (B, 6), 0, 128)}


def _trainer(cfg):
    model = SplitModel(cfg, SPLIT)
    params = model.init(KEY)
    return (SFPromptTrainer(model, ProtocolConfig()),
            {"head": params["head"], "body": params["body"]},
            {"tail": params["tail"], "prompt": params["prompt"]})


def _residuals(f, *args):
    """Shapes of what `jax.vjp` keeps for f's backward pass."""
    return [r.shape for r in jax.tree.leaves(
        jax.eval_shape(lambda *a: jax.vjp(f, *a)[1], *args))]


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)
                elif hasattr(sub, "jaxpr"):
                    yield from _primitives(sub.jaxpr)


REMAT = jax.make_jaxpr(jax.checkpoint(jnp.sin))(1.0).eqns[0].primitive


def test_body_keeps_no_scores_and_one_mlp_activation():
    cfg, batch = _vit()
    tr, frozen, trainable = _trainer(cfg)
    n_body = tr.model.body_cycles
    S = SPLIT.prompt_len + 1 + batch["patches"].shape[1]
    res = _residuals(lambda t: tr._split_loss(frozen, t, batch, KEY)[0],
                     trainable)
    # the layer scans stack their activations' residuals: (layers, B, ...)
    body = [s for s in res if len(s) >= 4 and s[:2] == (n_body, B)]
    assert body, res
    assert not [s for s in body if s[-2:] == (S, S)], body
    assert [s[-1] for s in body].count(cfg.d_ff) <= 1, body
    # the client's head and tail layers keep their probabilities
    assert [s for s in res if s[:2] == (1, B) and s[-2:] == (S, S)], res


@pytest.mark.parametrize("arch", [_vit, _causal_lm],
                         ids=["vit", "causal_lm"])
def test_phase2_grads_match_plain_body(arch):
    cfg, batch = arch()
    tr, frozen, trainable = _trainer(cfg)
    model = tr.model

    def plain_body(body_p, x, head_out, *, cache=None):
        """The body's layers one by one, with no checkpoint."""
        ctx, aux = model._ctx_from(head_out), jnp.float32(0.0)
        for i in range(model.body_cycles):
            for j, kind in enumerate(cfg.layer_pattern):
                p = jax.tree.map(lambda a: a[i], body_p["stack"][f"pos{j}"])
                x, _, a = apply_block(p, cfg, kind, x, ctx, None)
                aux = aux + a
        return {"smashed": x, "aux": aux, "cache": None}

    def grads():
        # the body's weights too: the SFL baselines train them
        return jax.jit(jax.grad(
            lambda fz, t: tr._split_loss(fz, t, batch, KEY)[0],
            argnums=(0, 1)))(frozen, trainable)

    with jax.default_matmul_precision("highest"):
        got = grads()
        model.body_fwd = plain_body
        want = grads()
    flat_got, flat_want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for g, w in zip(flat_got, flat_want):
        # a scan and a Python loop round differently, by ~1e-6 of a leaf's
        # largest entry, with or without the checkpoint
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-5,
                                   atol=1e-5 * np.abs(w).max())


def test_serving_programs_have_no_checkpoint():
    cfg, batch = _causal_lm()
    tr, frozen, trainable = _trainer(cfg)
    model = tr.model
    train = jax.make_jaxpr(
        lambda t: jax.grad(lambda t: tr._split_loss(frozen, t, batch,
                                                    KEY)[0])(t))(trainable)
    assert REMAT in set(_primitives(train.jaxpr))

    slots, page, nb = 2, 8, 4
    prefill = jax.make_jaxpr(make_tenant_prefill_step(model))(
        frozen, trainable["tail"], trainable["prompt"],
        {"tokens": batch["tokens"][:1]}, model.blank_slot_cache(nb * page))
    tails = jax.tree.map(lambda x: x[None], trainable["tail"])
    ints = jnp.zeros((slots,), jnp.int32)
    decode = jax.make_jaxpr(make_paged_multi_decode_step(model, 2))(
        frozen, tails, ints, ints, ints, ints + 2,
        model.init_paged_cache(slots * nb + 2, page),
        jnp.zeros((slots, nb), jnp.int32))
    for program in (prefill, decode):
        assert REMAT not in set(_primitives(program.jaxpr))
