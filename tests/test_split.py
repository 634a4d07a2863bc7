"""SplitModel: the three-way partition behaves like one model; the local
route (head->tail) skips the body. The per-architecture forward, train-step
and decode checks are in test_models.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.split import SplitConfig, SplitModel
from tests.test_models import SPLIT, build, make_batch

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ["stablelm-12b", "zamba2-2.7b",
                                  "phi3.5-moe-42b-a6.6b"])
def test_local_route_skips_body(arch):
    """Local route output is independent of the body parameters."""
    cfg, model = build(arch)
    params = model.init(KEY)
    batch = make_batch(cfg)
    out1 = model.forward(params, batch, route="local", mode="train")
    params2 = dict(params)
    params2["body"] = jax.tree.map(lambda x: x * 0.0 + 7.0, params["body"])
    out2 = model.forward(params2, batch, route="local", mode="train")
    np.testing.assert_array_equal(np.asarray(out1["logits"]),
                                  np.asarray(out2["logits"]))
    # ...but the split route IS affected
    out3 = model.forward(params2, batch, route="split", mode="train")
    assert np.abs(np.asarray(out3["logits"]) -
                  np.asarray(out1["logits"])).max() > 1e-4


def test_prompt_changes_output_and_grads_flow():
    """Prompts injected at the head must affect logits, and grads must flow
    back through the frozen body to the prompt (the phase-2 relay)."""
    cfg, model = build("stablelm-12b")
    params = model.init(KEY)
    batch = make_batch(cfg)

    def loss(prompt):
        out = model.forward(params, batch, route="split", mode="train",
                            prompt=prompt)
        return jnp.sum(out["logits"] ** 2)

    g = jax.grad(loss)(params["prompt"])
    assert float(jnp.abs(g).sum()) > 0


def test_segment_fractions():
    cfg, model = build("stablelm-12b")
    alpha, tau = model.segment_fractions()
    assert 0 < alpha < 1 and 0 < tau < 1 and alpha + tau < 1.2


def test_split_validation():
    cfg = get_config("stablelm-12b").reduced(n_layers=2)
    with pytest.raises(ValueError):
        SplitModel(cfg, SplitConfig(head_cycles=1, tail_cycles=1))


def test_whisper_cross_attention_uses_encoder():
    """Decoder logits must depend on the encoder output (cross-attention),
    and the split keeps the encoder client-side (in the head segment)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg, model = build("whisper-base")
    params = model.init(KEY)
    assert "encoder" in params["head"]          # client-side feature extractor
    B = 2
    toks = jax.random.randint(KEY, (B, 12), 0, cfg.vocab_size)
    fr1 = 0.05 * jax.random.normal(KEY, (B, cfg.encoder.n_frames, cfg.d_model))
    out1 = model.forward(params, {"tokens": toks, "frames": fr1},
                         route="split", mode="train")
    out2 = model.forward(params, {"tokens": toks, "frames": fr1 * -1.0},
                         route="split", mode="train")
    assert np.abs(np.asarray(out1["logits"] - out2["logits"])).max() > 1e-4


def test_comm_model_consistent_with_split_fractions():
    """The Table-1 cost model's alpha/tau must come from the real split."""
    from repro.core.comm import cost_inputs_from
    cfg, model = build("stablelm-12b")
    ci = cost_inputs_from(cfg, SPLIT, tokens_per_sample=64, D=100, model=model)
    a, t = model.segment_fractions()
    assert abs(ci.alpha - a) < 1e-9 and abs(ci.tau - t) < 1e-9
    assert ci.W == cfg.param_count()
