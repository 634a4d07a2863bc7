"""The transport-aware segment pipeline: codecs, boundaries, traffic meter,
per-client splits. Covers the codec round-trip error bounds, the custom-VJP
gradient wire, measured-vs-analytical byte accounting, int8 phase-2
convergence, and heterogeneous cut points."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ProtocolConfig, SFPromptTrainer, SplitConfig, SplitModel
from repro.core.comm import CostInputs, crosscheck
from repro.data import (DATASETS, iid_partition, stack_clients,
                        synthetic_image_dataset)
from repro.kernels.quant.ops import dequantize_int8, quantize_int8
from repro.runtime import (Boundary, Int8Codec, WireSpec,
                           get_codec)
from repro.runtime.hetero import ClientPlan, HeteroSFPromptTrainer

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------------ codecs
@pytest.mark.parametrize("name,bound", [
    ("fp32", 0.0),          # exact
    ("bf16", 2.0 ** -8),    # one bf16 mantissa step, relative
])
def test_codec_roundtrip_exactish(name, bound):
    codec = get_codec(name)
    x = jax.random.normal(KEY, (6, 33, 48)) * 5
    y = codec.roundtrip(x, 0.5, 0.5)
    err = jnp.max(jnp.abs(y - x) / jnp.maximum(jnp.abs(x), 1e-6))
    assert float(err) <= bound


@pytest.mark.parametrize("u_mode", ["stochastic", "nearest"])
def test_int8_roundtrip_within_quant_step(u_mode):
    codec = Int8Codec(impl="ref")
    x = jax.random.normal(KEY, (10, 64)) * 3
    u = (jax.random.uniform(jax.random.fold_in(KEY, 1), x.shape)
         if u_mode == "stochastic" else 0.5)
    values, scales = codec.encode(x, u)
    y = codec.decode((values, scales), x.dtype)
    step = scales  # one quant step per row
    max_err = jnp.max(jnp.abs(y - x) / step)
    # stochastic rounding errs < 1 step; nearest <= 0.5 step
    assert float(max_err) <= (1.0 if u_mode == "stochastic" else 0.5) + 1e-5


def test_int8_stochastic_rounding_unbiased():
    codec = Int8Codec(impl="ref")
    x = jax.random.normal(KEY, (4, 32)) * 2
    ys = []
    for i in range(64):
        u = jax.random.uniform(jax.random.fold_in(KEY, i), x.shape)
        ys.append(codec.decode(codec.encode(x, u), x.dtype))
    bias = jnp.mean(jnp.stack(ys), 0) - x
    scales = codec.encode(x, 0.5)[1]
    # empirical mean within a fraction of a quant step of the true value
    assert float(jnp.max(jnp.abs(bias) / scales)) < 0.2


# 12 and 20 rows (a batch-1 prefill) are not multiples of 8; 300 rows
# spans two padded row blocks
@pytest.mark.parametrize("rows", [40, 12, 20, 300])
def test_int8_kernel_matches_ref_bitwise(rows):
    """Pallas (interpret) quant/dequant == pure-jnp ref on the same noise."""
    x = jax.random.normal(KEY, (rows, 96)) * 3
    u = jax.random.uniform(jax.random.fold_in(KEY, 1), x.shape)
    vr, sr = quantize_int8(x, u, impl="ref")
    vi, si = quantize_int8(x, u, impl="interpret")
    np.testing.assert_array_equal(np.asarray(vr), np.asarray(vi))
    np.testing.assert_allclose(np.asarray(sr), np.asarray(si), rtol=1e-7)
    yr = dequantize_int8(vr, sr, impl="ref")
    yi = dequantize_int8(vi, si, impl="interpret")
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yi), rtol=1e-7)


def test_boundary_backward_gradient_is_quantized():
    """The custom VJP pushes the cotangent through the codec with the
    boundary's backward noise — the wire is int8 in BOTH directions."""
    codec = Int8Codec(impl="ref")
    b = Boundary("head_body", codec)
    x = jax.random.normal(KEY, (4, 8, 16)) * 2
    key = jax.random.PRNGKey(3)
    g = jax.random.normal(jax.random.fold_in(KEY, 2), x.shape)

    y, _ = b.transmit(x, key=key)
    _, vjp = jax.vjp(lambda t: b.transmit(t, key=key)[0], x)
    (gx,) = vjp(g)

    _, u_bwd = b._noise(key, x.shape)
    expected = codec.decode(codec.encode(g, u_bwd), g.dtype)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(expected),
                               rtol=1e-6, atol=1e-6)
    # and the forward value is a genuine int8 roundtrip, not identity
    assert float(jnp.max(jnp.abs(y - x))) > 0


def test_transmit_byte_counts():
    x = jnp.zeros((2, 10, 64))
    for name, per_elem, row_overhead in [("fp32", 4, 0), ("bf16", 2, 0),
                                         ("int8", 1, 4)]:
        b = Boundary("head_body", get_codec(name))
        _, nb_train = b.transmit(x, train=True)
        _, nb_infer = b.transmit(x, train=False)
        expect = 2 * 10 * 64 * per_elem + 2 * 10 * row_overhead
        assert int(nb_infer) == expect, name
        assert int(nb_train) == 2 * expect, name


# --------------------------------------------------- measured vs analytical
def _tiny_setup(codec_name, *, K=2, n_local=48, batch=8, seed=0, data=None):
    cfg = get_config("vit-base").reduced(n_layers=3, d_model=64, d_ff=128)
    split = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=4,
                        prune_gamma=0.3, local_epochs=1)
    wire = WireSpec.make(codec_name)
    model = SplitModel(cfg, split, wire)
    pcfg = ProtocolConfig(clients_per_round=K, local_epochs=1,
                          batch_size=batch, lr_local=0.01, lr_split=0.01,
                          momentum=0.0)
    tr = SFPromptTrainer(model, pcfg)
    if data is None:
        data = synthetic_image_dataset(DATASETS["cifar10-syn"], K * n_local,
                                       seed=seed, image_hw=32)
    data = {k: v[: K * n_local] for k, v in data.items()}
    clients = iid_partition(data, K, seed=0)
    cbatch = {k: jnp.asarray(v) for k, v in
              stack_clients(clients, list(range(K))).items()}
    return cfg, split, model, tr, cbatch, data


def test_meter_matches_analytical_within_5pct():
    """TrafficMeter's measured per-boundary bytes vs comm.sfprompt_comm's
    breakdown on reduced vit_base, int8 wire."""
    K, n_local, batch = 2, 48, 8
    cfg, split, model, tr, cbatch, _ = _tiny_setup("int8", K=K,
                                                   n_local=n_local,
                                                   batch=batch)
    state = tr.init(KEY)
    _, metrics = tr.round(state, cbatch)

    n_tokens = 1 + (32 // 16) ** 2 + split.prompt_len
    keep = max(batch, n_local - int(split.prune_gamma * n_local))
    keep -= keep % batch
    h, b, t = (model._segment_params_count(s)
               for s in ("head", "body", "tail"))
    W = h + b + t
    ci = CostInputs(W=W, alpha=h / W, tau=b / W,
                    q=n_tokens * cfg.d_model, D=n_local, U=1, E=1, K=K,
                    p=split.prompt_len * cfg.d_model,
                    gamma_keep=keep / n_local,
                    bytes_smashed=model.wire.head_body.codec.bytes_per_float(
                        (batch, n_tokens, cfg.d_model)))
    cc = crosscheck(tr.meter.totals, ci)
    assert set(cc) == {"head_body", "body_tail", "params"}
    for name, entry in cc.items():
        assert abs(entry["err_pct"]) <= 5.0, (name, entry)
    assert tr.meter.total_bytes() > 0


def test_meter_accumulates_rounds():
    _, _, _, tr, cbatch, _ = _tiny_setup("bf16")
    state = tr.init(KEY)
    state, m1 = tr.round(state, cbatch)
    per_round = dict(tr.meter.totals)
    state, m2 = tr.round(state, cbatch)
    assert tr.meter.rounds == 2
    for k, v in tr.meter.totals.items():
        np.testing.assert_allclose(v, 2 * per_round[k], rtol=1e-6)
    assert "wire/head_body_bytes" in m1 and m1["wire/head_body_bytes"] > 0


# --------------------------------------------------------- gradient flow
def test_phase2_converges_through_int8_wire():
    """Phase-2 training through the stochastic int8 boundary must still
    learn: split loss drops and eval accuracy lands within 1 point of the
    fp32-wire run from the same init/data. Eval uses a 480-sample superset
    of the training draw so 1 accuracy point spans ~5 samples."""
    K, n_local = 2, 96
    full = synthetic_image_dataset(DATASETS["cifar10-syn"], 480, seed=0,
                                   image_hw=32)
    results = {}
    for codec_name in ("fp32", "int8"):
        _, _, _, tr, cbatch, _ = _tiny_setup(codec_name, K=K,
                                             n_local=n_local, batch=8,
                                             data=full)
        state = tr.init(KEY)
        losses = []
        for _ in range(4):
            state, m = tr.round(state, cbatch)
            losses.append(m["split_loss"])
        ev = tr.evaluate(state["params"], full, batch_size=32)
        results[codec_name] = (losses, ev)

    for codec_name, (losses, ev) in results.items():
        assert losses[-1] < losses[0] * 0.95, (codec_name, losses)
        assert np.isfinite(ev["ce"])
    acc_fp32 = results["fp32"][1]["acc"]
    acc_int8 = results["int8"][1]["acc"]
    assert abs(acc_int8 - acc_fp32) <= 0.01 + 1e-6, (acc_fp32, acc_int8)


# ------------------------------------------------------------- hetero
def test_hetero_round_different_cut_points():
    """Two client groups with different head/tail cycle counts train in one
    round; the prompt is globally aggregated, tails stay per-group."""
    cfg = get_config("vit-base").reduced(n_layers=5, d_model=64, d_ff=128)
    plans = [
        ClientPlan(SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=4,
                               prune_gamma=0.0, local_epochs=1), 2, "phone"),
        ClientPlan(SplitConfig(head_cycles=2, tail_cycles=2, prompt_len=4,
                               prune_gamma=0.0, local_epochs=1), 2, "ws"),
    ]
    pcfg = ProtocolConfig(clients_per_round=2, local_epochs=1, batch_size=8,
                          lr_local=0.01, lr_split=0.01, momentum=0.0)
    ht = HeteroSFPromptTrainer(cfg, plans, pcfg, WireSpec.make("int8"))
    states = ht.init(KEY)
    # tails really differ across groups (different cut points)
    t0 = jax.tree.leaves(states[0]["params"]["tail"])
    t1 = jax.tree.leaves(states[1]["params"]["tail"])
    assert sum(x.size for x in t0) != sum(x.size for x in t1)

    data = synthetic_image_dataset(DATASETS["cifar10-syn"], 2 * 2 * 48,
                                   seed=0, image_hw=32)
    groups = []
    for g in range(2):
        part = {k: v[g * 96:(g + 1) * 96] for k, v in data.items()}
        clients = iid_partition(part, 2, seed=g)
        groups.append({k: jnp.asarray(v) for k, v in
                       stack_clients(clients, [0, 1]).items()})
    states, metrics = ht.round(states, groups)

    np.testing.assert_allclose(
        np.asarray(states[0]["params"]["prompt"]),
        np.asarray(states[1]["params"]["prompt"]), rtol=1e-6)
    assert metrics["wire/head_body_bytes"] > 0
    assert ht.meter.rounds == 1
    assert np.isfinite(metrics["phone/split_loss"])
    assert np.isfinite(metrics["ws/split_loss"])
    ev = ht.evaluate(states, data)
    assert np.isfinite(ev["ce"])


def test_hetero_rejects_mismatched_prompts():
    cfg = get_config("vit-base").reduced(n_layers=5, d_model=64, d_ff=128)
    plans = [ClientPlan(SplitConfig(prompt_len=4), 1),
             ClientPlan(SplitConfig(prompt_len=8), 1)]
    with pytest.raises(ValueError, match="prompt_len"):
        HeteroSFPromptTrainer(cfg, plans, ProtocolConfig())
