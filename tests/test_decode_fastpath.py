"""Decode fast path: multi-token scan stepping, the decode-attention
kernel, and KV-cache donation safety.

The scan path reuses the per-token decode body inside lax.scan, so the
identity tests pin that the amortization never changes a single logit; the
kernel tests sweep GQA / sliding-window / ragged per-slot lengths against
the jnp oracle; the donation tests replay a trace through donated caches
and require byte- and token-identical results (use-after-donate would
crash or corrupt)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SplitConfig, SplitModel
from repro.core.comm import serve_comm_breakdown
from repro.kernels.flash_attention.decode import (decode_attention,
                                                  paged_decode_attention)
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


@pytest.fixture(scope="module")
def setup():
    cfg, model = build_model()
    params = model.init(KEY)
    bank = TenantBank.replicate(params["tail"], params["prompt"], 3)
    return cfg, model, params, bank


# ragged max_new + staggered arrivals: slots join and retire mid-scan
REQS = [
    Request(rid=0, tenant=0, tokens=np.arange(9, dtype=np.int32) % 128,
            max_new=5, arrival=0),
    Request(rid=1, tenant=1, tokens=(np.arange(14, dtype=np.int32) * 3)
            % 128, max_new=11, arrival=0),
    Request(rid=2, tenant=2, tokens=(np.arange(6, dtype=np.int32) * 7)
            % 128, max_new=2, arrival=2),
    Request(rid=3, tenant=1, tokens=(np.arange(11, dtype=np.int32) * 5)
            % 128, max_new=7, arrival=3),
]


def run_engine(model, params, bank, *, decode_block, donate=True,
               reqs=REQS, n_slots=2):
    engine = ServeEngine(model, params, bank,
                         ServeConfig(n_slots=n_slots, max_seq=MAX_SEQ,
                                     decode_block=decode_block,
                                     donate=donate),
                         collect_logits=True)
    stats = engine.run(reqs)
    return {f.req.rid: f for f in stats["finished"]}, stats


# ------------------------------------------------------- scan stepping
def test_scan_decode_logit_identical_to_per_token(setup):
    """decode_block=8 (scan stepping, power-of-two buckets, deferred
    retirement) produces the same tokens AND fp32 logits as per-token
    dispatch for every request in a ragged 4-request trace."""
    cfg, model, params, bank = setup
    per_tok, s1 = run_engine(model, params, bank, decode_block=1)
    scanned, s8 = run_engine(model, params, bank, decode_block=8)
    assert set(per_tok) == set(scanned) == {r.rid for r in REQS}
    for rid in per_tok:
        np.testing.assert_array_equal(per_tok[rid].tokens,
                                      scanned[rid].tokens,
                                      err_msg=f"rid={rid}")
        np.testing.assert_allclose(per_tok[rid].logits, scanned[rid].logits,
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=f"rid={rid}")
    # every generated token was delivered, none invented by garbage steps
    assert s1["tokens_out"] == s8["tokens_out"] == sum(
        r.max_new for r in REQS)


def test_scan_decode_wire_bytes_match_per_token(setup):
    """Deferred retirement must not meter dead slots: the scan path's
    measured bytes equal the per-token path's exactly (the per-step
    `remaining > t` mask stops counting a slot the moment it retires)."""
    cfg, model, params, bank = setup
    _, s1 = run_engine(model, params, bank, decode_block=1)
    _, s8 = run_engine(model, params, bank, decode_block=8)
    for name in ("head_body", "body_tail", "total"):
        assert s1["wire_bytes"][name] == pytest.approx(
            s8["wire_bytes"][name]), name


@pytest.mark.parametrize("wire", ["fp32", "int8"])
def test_scan_decode_metered_vs_analytical(wire):
    """The analytical per-token serve model still matches within 5% when
    tokens are generated through the scanned fast path."""
    cfg, model = build_model(wire)
    params = model.init(KEY)
    bank = TenantBank.replicate(params["tail"], params["prompt"], 2)
    wl = WorkloadConfig(n_requests=6, mean_interarrival=1.0,
                        prompt_choices=(6, 10), new_token_choices=(3, 5),
                        n_tenants=2, vocab_size=cfg.vocab_size, seed=3)
    reqs = synthetic_requests(wl)
    engine = ServeEngine(model, params, bank,
                         ServeConfig(n_slots=3, max_seq=MAX_SEQ,
                                     decode_block=4))
    stats = engine.run(reqs)
    analytical = serve_comm_breakdown(
        model.wire, d_model=cfg.d_model, soft_prompt_len=PROMPT_LEN,
        requests=[(len(r.tokens), r.max_new) for r in reqs])
    for name, ref in analytical.items():
        got = stats["wire_bytes"][name]
        assert ref > 0
        assert abs(got - ref) / ref <= 0.05, (name, got, ref)


# --------------------------------------------------- decode attention
def _ragged_cache(B, W, Hkv, D, lens, *, ring=False):
    k = jax.random.normal(jax.random.PRNGKey(1), (B, W, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, W, Hkv, D))
    pos = np.full((B, W), -1, np.int32)
    for b, L in enumerate(lens):
        slots = (np.arange(L) + 3 * b) % W if ring else np.arange(L)
        pos[b, slots] = np.arange(L)
    return k, v, jnp.asarray(pos)


def _as_page_pool(k, v, pos, page):
    """The dense (B, W) caches scattered into a shuffled page pool with the
    two reserved pages in front: (k_pool, v_pool, block_tables, pool_pos)."""
    B, W = pos.shape
    nb = W // page
    P = 2 + B * nb
    perm = np.random.default_rng(7).permutation(np.arange(2, P))
    tables = perm.reshape(B, nb)

    def pool(x):
        out = np.zeros((P, page) + x.shape[2:], np.asarray(x).dtype)
        out[tables] = np.asarray(x).reshape((B, nb, page) + x.shape[2:])
        return out

    pool_pos = np.full((P, page), -1, np.int32)
    pool_pos[tables] = np.asarray(pos).reshape(B, nb, page)
    return (jnp.asarray(pool(k)), jnp.asarray(pool(v)),
            jnp.asarray(tables, jnp.int32), jnp.asarray(pool_pos))


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("kw", [
    {},
    dict(sliding_window=16),
    dict(softcap=10.0),
    dict(sliding_window=9, softcap=5.0),
    # a window that is not a block multiple: several kv blocks per slot,
    # the query positions read from the scalar-prefetch operand
    dict(W=300),
    # the paged layout: (P, 1, page) pool positions, block tables
    dict(page=16),
    dict(page=16, sliding_window=9, softcap=5.0),
])
def test_decode_attention_kernel_vs_ref(Hq, Hkv, kw):
    """Pallas decode kernel (interpret) and the grouped XLA path vs the
    jnp oracle, across GQA ratios, sliding windows, softcap, ragged
    ring-ordered per-slot lengths, multi-block windows and the paged
    pool."""
    kw = dict(kw)
    W, page = kw.pop("W", 64), kw.pop("page", None)
    B, D = 3, 32
    lens = [7, 33, W]
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, Hq, D))
    k, v, pos = _ragged_cache(B, W, Hkv, D, lens, ring=True)
    qpos = jnp.asarray([L - 1 for L in lens], jnp.int32)
    ref = decode_attention(q, k, v, q_positions=qpos, kv_positions=pos,
                           impl="ref", **kw)
    for impl in ("xla", "interpret"):
        if page is None:
            out = decode_attention(q, k, v, q_positions=qpos,
                                   kv_positions=pos, impl=impl, **kw)
        else:
            k_pool, v_pool, tables, pool_pos = _as_page_pool(k, v, pos, page)
            out = paged_decode_attention(
                q, k_pool, v_pool, block_tables=tables, q_positions=qpos,
                kv_positions=pool_pos, impl=impl, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"impl={impl} {kw}")


def test_flash_attention_auto_routes_decode_to_fast_path():
    """impl='auto' off-TPU must reach the grouped decode path for Sq=1
    cache reads (bit-identical to decode_attention impl='xla'), not fall
    back to the oracle before the decode dispatch."""
    B, W, Hq, Hkv, D = 2, 32, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, Hq, D))
    k, v, pos = _ragged_cache(B, W, Hkv, D, [9, 25], ring=True)
    qpos = jnp.asarray([8, 24], jnp.int32)
    from repro.kernels.flash_attention.ops import flash_attention
    auto = flash_attention(q, k, v, q_offset=qpos, kv_positions=pos,
                           impl="auto")
    xla = decode_attention(q, k, v, q_positions=qpos, kv_positions=pos,
                           impl="xla")
    assert jax.default_backend() == "cpu"
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(xla))


def test_decode_attention_rejects_multi_query():
    B, W, H, D = 1, 16, 2, 8
    q = jnp.zeros((B, 3, H, D))
    k = jnp.zeros((B, W, H, D))
    pos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None], (B, W))
    with pytest.raises(AssertionError):
        decode_attention(q, k, k, q_positions=jnp.zeros((B,), jnp.int32),
                         kv_positions=pos, impl="xla")


def test_decode_attention_empty_slots_ignored():
    """Cache rows marked -1 must contribute nothing, whatever they hold."""
    B, W, H, D = 2, 32, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, D))
    k, v, pos = _ragged_cache(B, W, H, D, [5, 20])
    poison = jnp.where((pos == -1)[..., None, None], 1e6, 0.0)
    ref = decode_attention(q, k, v, q_positions=jnp.asarray([4, 19]),
                           kv_positions=pos, impl="ref")
    for impl in ("xla", "interpret"):
        out = decode_attention(q, k + poison, v + poison,
                               q_positions=jnp.asarray([4, 19]),
                               kv_positions=pos, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ donation
def test_donated_engine_matches_undonated(setup):
    """Cache donation must be invisible to results: same tokens, logits,
    and measured wire bytes with donation on and off."""
    cfg, model, params, bank = setup
    with_d, sd = run_engine(model, params, bank, decode_block=4,
                            donate=True)
    without, sn = run_engine(model, params, bank, decode_block=4,
                             donate=False)
    for rid in with_d:
        np.testing.assert_array_equal(with_d[rid].tokens,
                                      without[rid].tokens)
        np.testing.assert_array_equal(with_d[rid].logits,
                                      without[rid].logits)
    assert sd["wire_bytes"]["total"] == pytest.approx(
        sn["wire_bytes"]["total"])


def test_donated_replay_after_reset_stats(setup):
    """No use-after-donate: one warm engine (donated caches, scan path)
    re-serves the trace after reset_stats() with identical counters,
    tokens, and meter totals."""
    cfg, model, params, bank = setup
    engine = ServeEngine(model, params, bank,
                         ServeConfig(n_slots=2, max_seq=MAX_SEQ,
                                     decode_block=8, donate=True))
    first = engine.run(REQS)
    snap1 = (engine.decode_steps, engine.tokens_out, engine.prefill_count,
             first["wire_bytes"]["total"])
    engine.reset_stats()
    second = engine.run(REQS)
    snap2 = (engine.decode_steps, engine.tokens_out, engine.prefill_count,
             second["wire_bytes"]["total"])
    assert snap1 == snap2
    toks1 = {f.req.rid: f.tokens.tolist() for f in first["finished"]}
    toks2 = {f.req.rid: f.tokens.tolist() for f in second["finished"]}
    assert toks1 == toks2


def test_launch_steps_donated_cache_matches(setup):
    """launch/steps.py donate_cache=True: prefill+decode through donated
    caches equals the undonated jitted path bit-for-bit."""
    cfg, model, params, bank = setup
    tokens = jnp.asarray(np.arange(7, dtype=np.int32)[None] % 128)

    def roll(donate):
        prefill = (make_prefill_step(model, dtype=jnp.float32,
                                     donate_cache=True) if donate
                   else jax.jit(make_prefill_step(model,
                                                  dtype=jnp.float32)))
        decode = (make_decode_step(model, dtype=jnp.float32,
                                   donate_cache=True) if donate
                  else jax.jit(make_decode_step(model, dtype=jnp.float32)))
        cache = model.init_cache(1, seq_len=MAX_SEQ)
        logits, cache = prefill(params, {"tokens": tokens}, cache)
        outs = [np.asarray(logits)]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = jnp.asarray([7 + PROMPT_LEN], jnp.int32)
        for i in range(3):
            tok, logits, cache = decode(
                params, {"tokens": tok[:, None], "pos": pos + i}, cache)
            outs.append(np.asarray(logits))
        return np.concatenate(outs)

    np.testing.assert_array_equal(roll(donate=False), roll(donate=True))
