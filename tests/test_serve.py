"""Serving-engine correctness: continuous batching is logit-equivalent to
sequential decoding, tenants are isolated, and the metered wire traffic
matches the analytical per-token model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SplitConfig, SplitModel
from repro.core.comm import serve_comm_breakdown
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.runtime import WireSpec
from repro.serve import (Request, ServeConfig, ServeEngine, TenantBank,
                         WorkloadConfig, synthetic_requests)

KEY = jax.random.PRNGKey(0)
MAX_SEQ = 48
PROMPT_LEN = 4


def build_model(wire="fp32"):
    cfg = get_config("qwen2.5-14b").reduced(
        n_layers=3, d_model=64, d_ff=128, vocab_size=128)
    split = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=PROMPT_LEN)
    return cfg, SplitModel(cfg, split, WireSpec.make(wire))


def make_bank(model, params, n_tenants=3, jitter=0.2):
    """Distinct per-tenant (tail, prompt) so cross-tenant leakage would
    actually change logits."""
    tails, prompts = [], []
    for t in range(n_tenants):
        key = jax.random.fold_in(jax.random.PRNGKey(7), t)
        leaves, treedef = jax.tree.flatten(params["tail"])
        ks = jax.random.split(key, len(leaves) + 1)
        tails.append(jax.tree.unflatten(treedef, [
            x + jitter * jax.random.normal(k, x.shape, x.dtype)
            for x, k in zip(leaves, ks[:-1])]))
        prompts.append(params["prompt"] + jitter * jax.random.normal(
            ks[-1], params["prompt"].shape))
    return TenantBank.from_lists(tails, prompts)


@pytest.fixture(scope="module")
def setup():
    cfg, model = build_model()
    params = model.init(KEY)
    bank = make_bank(model, params)
    return cfg, model, params, bank


def sequential_reference(cfg, model, params, bank, req):
    """Per-request batch=1 prefill + decode with the request's tenant
    (tail, prompt) — the no-batching ground truth, fp32 activations."""
    p = {"head": params["head"], "body": params["body"],
         "tail": bank.tail(req.tenant), "prompt": bank.prompt(req.tenant)}
    prefill = jax.jit(make_prefill_step(model, dtype=jnp.float32))
    decode = jax.jit(make_decode_step(model, dtype=jnp.float32))
    cache = model.init_cache(1, seq_len=MAX_SEQ)
    logits, cache = prefill(p, {"tokens": jnp.asarray(req.tokens)[None]},
                            cache)
    toks = [int(jnp.argmax(logits[0]))]
    outs = [np.asarray(logits[0], np.float32)]
    pos0 = len(req.tokens) + PROMPT_LEN
    for i in range(req.max_new - 1):
        tok = jnp.asarray([[toks[-1]]], jnp.int32)
        posi = jnp.asarray([pos0 + i], jnp.int32)
        _, logits, cache = decode(p, {"tokens": tok, "pos": posi}, cache)
        toks.append(int(jnp.argmax(logits[0])))
        outs.append(np.asarray(logits[0], np.float32))
    return np.asarray(toks, np.int32), np.stack(outs)


REQS = [
    Request(rid=0, tenant=0, tokens=np.arange(9, dtype=np.int32) % 128,
            max_new=5, arrival=0),
    Request(rid=1, tenant=1, tokens=(np.arange(14, dtype=np.int32) * 3)
            % 128, max_new=4, arrival=0),
    Request(rid=2, tenant=2, tokens=(np.arange(6, dtype=np.int32) * 7)
            % 128, max_new=6, arrival=2),
    Request(rid=3, tenant=1, tokens=(np.arange(11, dtype=np.int32) * 5)
            % 128, max_new=3, arrival=3),
]


def test_batched_continuous_matches_sequential(setup):
    """4 requests, 2 slots: queueing + mid-flight joins + slot reuse.
    Every request's greedy tokens AND per-step logits equal its standalone
    sequential decode at fp32."""
    cfg, model, params, bank = setup
    engine = ServeEngine(model, params, bank,
                         ServeConfig(n_slots=2, max_seq=MAX_SEQ),
                         collect_logits=True)
    stats = engine.run(REQS)
    assert stats["n_finished"] == len(REQS)
    by_rid = {f.req.rid: f for f in stats["finished"]}
    for req in REQS:
        want_toks, want_logits = sequential_reference(
            cfg, model, params, bank, req)
        got = by_rid[req.rid]
        np.testing.assert_array_equal(got.tokens, want_toks,
                                      err_msg=f"rid={req.rid}")
        np.testing.assert_allclose(got.logits, want_logits,
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"rid={req.rid}")


def test_tenant_isolation_mid_batch_join(setup):
    """Tenant A's outputs are bit-identical whether or not tenant B's
    request joins the batch mid-flight."""
    cfg, model, params, bank = setup
    a = Request(rid=0, tenant=0,
                tokens=np.arange(8, dtype=np.int32), max_new=6, arrival=0)
    b = Request(rid=1, tenant=2,
                tokens=(np.arange(12, dtype=np.int32) * 11) % 128,
                max_new=4, arrival=2)

    def run(reqs):
        eng = ServeEngine(model, params, bank,
                          ServeConfig(n_slots=2, max_seq=MAX_SEQ),
                          collect_logits=True)
        return {f.req.rid: f for f in eng.run(reqs)["finished"]}

    alone = run([a])[0]
    joined = run([a, b])[0]
    np.testing.assert_array_equal(alone.tokens, joined.tokens)
    np.testing.assert_array_equal(alone.logits, joined.logits)


@pytest.mark.parametrize("wire", ["fp32", "int8"])
def test_metered_serve_bytes_match_analytical(wire):
    """Engine-measured wire traffic vs `serve_comm_breakdown` <= 5% per
    boundary (decode bytes counted per OCCUPIED slot only)."""
    cfg, model = build_model(wire)
    params = model.init(KEY)
    bank = make_bank(model, params, n_tenants=2)
    wl = WorkloadConfig(n_requests=6, mean_interarrival=1.0,
                        prompt_choices=(6, 10), new_token_choices=(3, 5),
                        n_tenants=2, vocab_size=cfg.vocab_size, seed=3)
    reqs = synthetic_requests(wl)
    engine = ServeEngine(model, params, bank,
                         ServeConfig(n_slots=3, max_seq=MAX_SEQ))
    stats = engine.run(reqs)
    analytical = serve_comm_breakdown(
        model.wire, d_model=cfg.d_model, soft_prompt_len=PROMPT_LEN,
        requests=[(len(r.tokens), r.max_new) for r in reqs])
    for name, ref in analytical.items():
        got = stats["wire_bytes"][name]
        assert ref > 0
        assert abs(got - ref) / ref <= 0.05, (name, got, ref)
    assert stats["wire_per_token"]["total"] == pytest.approx(
        stats["wire_bytes"]["total"] / stats["tokens_out"])


def test_reset_stats_replays_trace_identically(setup):
    """reset_stats() lets one warm engine re-serve a trace from step 0
    with clean counters — same schedule, same tokens, same meter."""
    cfg, model, params, bank = setup
    engine = ServeEngine(model, params, bank,
                         ServeConfig(n_slots=2, max_seq=MAX_SEQ))
    first = engine.run(REQS)
    snap1 = (engine.decode_steps, engine.tokens_out, engine.prefill_count,
             first["wire_bytes"]["total"])
    engine.reset_stats()
    assert engine.decode_steps == 0 and engine.tokens_out == 0
    second = engine.run(REQS)
    snap2 = (engine.decode_steps, engine.tokens_out, engine.prefill_count,
             second["wire_bytes"]["total"])
    assert snap1 == snap2
    toks1 = {f.req.rid: f.tokens.tolist() for f in first["finished"]}
    toks2 = {f.req.rid: f.tokens.tolist() for f in second["finished"]}
    assert toks1 == toks2
    # guard: resetting mid-flight is an error
    engine.submit(REQS[0])
    engine.step()
    with pytest.raises(RuntimeError):
        engine.reset_stats()


def test_slot_cache_write_read_roundtrip(setup):
    cfg, model, params, bank = setup
    shared = model.init_cache(3, seq_len=16)
    single = jax.tree.map(
        lambda x: jnp.full_like(x, 3.0) if jnp.issubdtype(
            x.dtype, jnp.floating) else jnp.full_like(x, 3),
        model.blank_slot_cache(16))
    written = model.cache_write_slot(shared, single, jnp.int32(1))
    back = model.cache_read_slot(written, jnp.int32(1))
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(single)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the other slots are untouched
    other = model.cache_read_slot(written, jnp.int32(0))
    for a, b in zip(jax.tree.leaves(other),
                    jax.tree.leaves(model.cache_read_slot(shared, 0))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_admission_control_and_validation(setup):
    cfg, model, params, bank = setup
    engine = ServeEngine(model, params, bank,
                         ServeConfig(n_slots=1, max_seq=MAX_SEQ,
                                     max_queue=2))
    mk = lambda rid: Request(rid=rid, tenant=0,
                             tokens=np.arange(4, dtype=np.int32),
                             max_new=2, arrival=0)
    assert engine.submit(mk(0)) and engine.submit(mk(1))
    assert not engine.submit(mk(2))          # queue full -> rejected
    assert engine.rejected == 1
    with pytest.raises(ValueError):          # window overflow
        engine.submit(Request(rid=9, tenant=0,
                              tokens=np.zeros(MAX_SEQ, np.int32),
                              max_new=8, arrival=0))
    with pytest.raises(ValueError):          # unknown tenant
        engine.submit(Request(rid=10, tenant=99,
                              tokens=np.arange(4, dtype=np.int32),
                              max_new=2, arrival=0))


def test_workload_is_pure_function_of_seed():
    wl = WorkloadConfig(n_requests=12, seed=5)
    a, b = synthetic_requests(wl), synthetic_requests(wl)
    assert [(r.arrival, r.tenant, r.max_new) for r in a] == \
           [(r.arrival, r.tenant, r.max_new) for r in b]
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.tokens, rb.tokens)
    c = synthetic_requests(WorkloadConfig(n_requests=12, seed=6))
    assert any(not np.array_equal(ra.tokens, rc.tokens)
               for ra, rc in zip(a, c))


def test_engine_rejects_non_token_archs():
    cfg = get_config("vit-base").reduced(n_layers=3, d_model=64, d_ff=128)
    model = SplitModel(cfg, SplitConfig(head_cycles=1, tail_cycles=1,
                                        prompt_len=4))
    params = model.init(KEY)
    with pytest.raises(ValueError):
        ServeEngine(model, params,
                    TenantBank.replicate(params["tail"], params["prompt"],
                                         2),
                    ServeConfig(n_slots=2, max_seq=32))


def test_launcher_cuts_depth_only_at_published_widths():
    from repro.launch.serve import served_config
    pub = get_config("qwen2.5-14b")
    cut = served_config("qwen2.5-14b", reduced=False, layers=3)
    assert cut.n_layers == 3 and cut.name == "qwen2.5-14b-3L"
    assert dataclasses.replace(cut, name=pub.name,
                               n_layers=pub.n_layers) == pub
    assert served_config("qwen2.5-14b", reduced=False) == pub
    small = served_config("qwen2.5-14b")
    assert small.n_layers == 3 and small.d_model < pub.d_model


def test_launcher_paged_bytes_exact_with_logits():
    """The launcher's paged run meters the analytical bytes less exactly
    one [soft prompt | prefix] payload per prefix hit, and hands back each
    request's logits and its engine."""
    from repro.launch import serve
    stats = serve.main([
        "--requests", "6", "--slots", "2", "--tenants", "1", "--wire",
        "int8", "--prompt-choices", "8", "12", "--new-token-choices", "2",
        "4", "--page-size", "8", "--shared-prefix", "11",
        "--prefill-chunk", "4", "--collect-logits", "--impl", "ref"])
    model = stats["engine"].model
    hit = serve_comm_breakdown(model.wire, d_model=model.cfg.d_model,
                               soft_prompt_len=model.split.prompt_len,
                               requests=[(11, 1)])
    assert stats["prefix_hits"] > 0
    for name, want in stats["wire_analytical"].items():
        assert stats["wire_bytes"][name] == \
            want - stats["prefix_hits"] * hit[name]
    for f in stats["finished"]:
        assert f.logits.shape == (f.req.max_new, model.cfg.vocab_size)
