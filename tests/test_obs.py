"""Observability contract: the flight recorder observes, never
participates.

Pins the three promises repro.obs makes (see obs/trace.py):
  * tracing OFF is bit-identical — round params, metered bytes, served
    tokens/logits all match an untraced run exactly;
  * tracing ON accounts bytes EXACTLY — per-stream sums over the
    `meter.absorb` events equal the TrafficMeter totals with ==;
  * traces are deterministic modulo wall time — two same-seed runs
    produce equal records once `strip_times` removes t_ns/dur_ns.

Plus the satellite contracts: sharding fallbacks surface as ONE
structured event per drain (warnings path intact), the TrafficMeter
state_dict round-trips (including wall streams and legacy restores),
and the exporters / tools/trace_check.py validate what the launchers
actually write.
"""
import importlib.util
import io
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ProtocolConfig, SFPromptTrainer, SplitConfig, SplitModel
from repro.core.aggregation import get_aggregator
from repro.data import (DATASETS, iid_partition, select_clients,
                        stack_clients, synthetic_image_dataset)
from repro.fed import ClientSampler, FederatedEngine, Population
from repro.launch.mesh import report_sharding_fallbacks
from repro.obs import (LEVELS, MetricsRegistry, NOOP, Tracer, chrome_trace,
                       make_tracer, prometheus_text, span_tree, strip_times,
                       sum_stream)
from repro.obs.export import meter_final_record, write_jsonl
from repro.runtime import WireSpec
from repro.runtime.meter import TrafficMeter, WALL_STREAMS
from repro.serve import (PagedServeConfig, PagedServeEngine, Request,
                         TenantBank)
from repro.sharding import rules
from repro.sharding.rules import params_pspecs, pop_sharding_fallbacks

KEY = jax.random.PRNGKey(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ fixtures
@pytest.fixture(scope="module")
def proto_setup():
    cfg = get_config("vit-base").reduced(n_layers=3, d_model=64, d_ff=128)
    split = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=4,
                        prune_gamma=0.5, local_epochs=1)
    model = SplitModel(cfg, split)
    data = synthetic_image_dataset(DATASETS["cifar10-syn"], 160, seed=0,
                                   image_hw=32)
    clients = iid_partition(data, 6, seed=0)
    return model, clients


def _trainer(model, tracer=None, k=2, aggregator=None):
    pcfg = ProtocolConfig(clients_per_round=k, local_epochs=1, batch_size=8,
                          lr_local=0.05, lr_split=0.05, momentum=0.0)
    return SFPromptTrainer(model, pcfg, aggregator, tracer=tracer)


def _run_rounds(model, clients, tracer=None, n=2, k=2):
    tr = _trainer(model, tracer, k)
    state = tr.init(KEY)
    for r in range(n):
        idx = select_clients(len(clients), k, seed=0, round_idx=r)
        batch = {kk: jnp.asarray(v) for kk, v in
                 stack_clients(clients, idx).items()}
        state, _ = tr.round(state, batch)
    return tr, state


def _engine(model, clients, tracer=None, k=2):
    """A FederatedEngine over the fixture's clients; the trainer and the
    engine share `tracer`, as the train launcher wires them."""
    eng = FederatedEngine(_trainer(model, tracer, k),
                          Population.from_client_list(clients),
                          ClientSampler(len(clients), k, seed=0),
                          tracer=tracer)
    eng.init(KEY)
    return eng


def _run_engine(model, clients, tracer=None, n=2, k=2):
    eng = _engine(model, clients, tracer, k)
    metrics = [eng.run_round()[1] for _ in range(n)]
    return eng.trainer, eng.state, metrics


@pytest.fixture(scope="module")
def serve_setup():
    cfg = get_config("qwen2.5-14b").reduced(
        n_layers=3, d_model=64, d_ff=128, vocab_size=128)
    split = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=4)
    model = SplitModel(cfg, split, WireSpec.make("fp32"))
    params = model.init(KEY)
    tails, prompts = [], []
    for t in range(2):
        key = jax.random.fold_in(jax.random.PRNGKey(7), t)
        leaves, treedef = jax.tree.flatten(params["tail"])
        ks = jax.random.split(key, len(leaves) + 1)
        tails.append(jax.tree.unflatten(treedef, [
            x + 0.2 * jax.random.normal(kk, x.shape, x.dtype)
            for x, kk in zip(leaves, ks[:-1])]))
        prompts.append(params["prompt"] + 0.2 * jax.random.normal(
            ks[-1], params["prompt"].shape))
    bank = TenantBank.from_lists(tails, prompts)
    return model, params, bank


def _toks(n, mult):
    return (np.arange(n, dtype=np.int32) * mult) % 128


SERVE_REQS = [
    Request(rid=0, tenant=0, tokens=_toks(9, 1), max_new=4, arrival=0),
    Request(rid=1, tenant=1, tokens=_toks(12, 3), max_new=3, arrival=0),
    Request(rid=2, tenant=1, tokens=_toks(6, 7), max_new=4, arrival=2),
]


def _run_serve(model, params, bank, tracer=None):
    eng = PagedServeEngine(
        model, params, bank,
        PagedServeConfig(n_slots=2, max_seq=48, decode_block=2,
                         page_size=8, shared_prefix=(3, 5, 7, 11),
                         prefill_chunk=8),
        collect_logits=True, tracer=tracer)
    return eng, eng.run(list(SERVE_REQS))


def _trees_equal(a, b):
    return all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ----------------------------------------- tracing off == never happened
@pytest.mark.parametrize("entry", ["trainer", "engine"])
def test_round_bit_identical_with_and_without_tracing(proto_setup, entry):
    """Headline criterion half 1: a traced round computes the SAME
    params, metrics and metered bytes as an untraced one, whether the
    trainer is driven directly or by the FederatedEngine (whose stage
    spans wrap sampling, the gather and the copy to the device)."""
    model, clients = proto_setup
    if entry == "trainer":
        (tr_off, st_off), (tr_on, st_on) = (
            _run_rounds(model, clients, tracer=t)
            for t in (None, Tracer("step")))
    else:
        (tr_off, st_off, m_off), (tr_on, st_on, m_on) = (
            _run_engine(model, clients, tracer=t)
            for t in (None, Tracer("step")))
        assert m_off == m_on                  # every metric, exact floats
    assert tr_off.tracer is NOOP          # default wiring
    assert _trees_equal(st_off["params"], st_on["params"])
    assert tr_off.meter.totals == tr_on.meter.totals   # exact floats
    assert tr_off.tracer.records() == ()  # and recorded nothing
    assert len(tr_on.tracer.records()) > 0


def test_serve_bit_identical_with_and_without_tracing(serve_setup):
    """...and the paged serve engine: greedy tokens, per-step logits,
    and metered wire bytes are unchanged by tracing."""
    model, params, bank = serve_setup
    _, off = _run_serve(model, params, bank)
    eng_on, on = _run_serve(model, params, bank, tracer=Tracer("step"))
    offs = {f.req.rid: f for f in off["finished"]}
    ons = {f.req.rid: f for f in on["finished"]}
    assert set(offs) == set(ons) == {r.rid for r in SERVE_REQS}
    for rid in offs:
        np.testing.assert_array_equal(np.asarray(offs[rid].tokens),
                                      np.asarray(ons[rid].tokens))
        np.testing.assert_array_equal(np.asarray(offs[rid].logits),
                                      np.asarray(ons[rid].logits))
    assert off["wire_bytes"] == on["wire_bytes"]   # exact floats
    assert len(eng_on.tracer.records()) > 0


# -------------------------------------------------- exact byte accounting
def test_round_trace_bytes_sum_exactly_to_meter(proto_setup):
    """Headline criterion half 2: per-stream sums over the meter.absorb
    events equal the TrafficMeter totals with ==, not allclose."""
    model, clients = proto_setup
    tr, _ = _run_rounds(model, clients, tracer=Tracer("step"), n=3)
    recs = tr.tracer.records()
    for stream, total in tr.meter.totals.items():
        assert sum_stream(recs, "meter.absorb", stream) == total
    # the round spans carry the same folded floats as attributes
    spans = [r for r in recs if r["name"] == "sfprompt.round"]
    assert len(spans) == 3
    for stream in ("head_body", "body_tail", "params"):
        assert sum(s["attrs"][stream] for s in spans) == \
            tr.meter.totals[stream]


def test_serve_trace_bytes_sum_exactly_to_meter(serve_setup):
    model, params, bank = serve_setup
    eng, _ = _run_serve(model, params, bank, tracer=Tracer("step"))
    recs = eng.tracer.records()
    for stream, total in eng.meter.totals.items():
        assert sum_stream(recs, "meter.absorb", stream) == total


# ------------------------------------------------- spans along the round
ROUND_TREE = [(1, "fed.sample"), (1, "fed.gather"), (1, "fed.put"),
              (2, "sfprompt.dispatch"), (2, "sfprompt.sync"),
              (1, "sfprompt.round"), (1, "fed.writeback"), (0, "fed.round")]


def test_engine_round_span_tree(proto_setup):
    """One traced round is exactly the stage spans, nested as listed
    (records push at exit, so children precede their parent), the gather
    and the copy to the device carry the cohort's bytes, and the gather
    names the path it took: the device, where the data lives."""
    model, clients = proto_setup
    eng = _engine(model, clients, Tracer("round"))
    eng.run_round()
    recs = eng.tracer.records()
    assert [(d, n) for d, n, _ in span_tree(recs)] == ROUND_TREE
    spans = {r["name"]: r for r in recs if r["kind"] == "span"}
    cohort = eng.cohort_history[0]
    nbytes = sum(v.nbytes for v in eng.population.gather(cohort).values())
    assert spans["fed.gather"]["attrs"] == {"bytes": nbytes,
                                            "where": "device"}
    assert spans["fed.put"]["attrs"] == {"bytes": nbytes}
    assert spans["fed.round"]["attrs"] == {"round": 0, "cohort": 2}


def test_spans_are_profiler_annotations(proto_setup, tmp_path):
    """Under a profiler session the live tracer's spans land on the
    /host:CPU plane by name, on the profiler's clock."""
    model, clients = proto_setup
    eng = _engine(model, clients, Tracer("round"))
    eng.run_round()                       # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_round()
        jax.block_until_ready(eng.params)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    plane = jax.profiler.ProfileData.from_file(str(path)) \
        .find_plane_with_name("/host:CPU")
    names = {e.name for line in plane.lines for e in line.events}
    assert {n for _, n in ROUND_TREE} <= names


@pytest.mark.parametrize("secure", [False, True])
def test_round_program_names_its_phases(proto_setup, secure):
    """The lowered round carries the four phase scopes in its op
    metadata, on the clear and the secure aggregation path."""
    model, clients = proto_setup
    agg = get_aggregator(secure=True, impl="ref") if secure else None
    tr = _trainer(model, aggregator=agg)
    batch = {kk: jnp.asarray(v) for kk, v in
             stack_clients(clients, [0, 1]).items()}
    ones = jnp.ones((2,), jnp.float32)
    text = tr._round_jit.lower(
        tr.init(KEY), batch, {"transmit": ones, "aggregate": ones},
        None).as_text(debug_info=True)
    for scope in ("sfprompt.phase1_local", "sfprompt.phase1b_prune",
                  "sfprompt.phase2_split", "sfprompt.phase3_aggregate"):
        assert f"/{scope}/" in text, scope


# ------------------------------------------------------------ determinism
def test_round_trace_deterministic_modulo_walltime(proto_setup):
    model, clients = proto_setup
    tr1, _ = _run_rounds(model, clients, tracer=Tracer("step"))
    tr2, _ = _run_rounds(model, clients, tracer=Tracer("step"))
    assert strip_times(tr1.tracer.records()) == \
        strip_times(tr2.tracer.records())


def test_serve_trace_deterministic_modulo_walltime(serve_setup):
    model, params, bank = serve_setup
    eng1, _ = _run_serve(model, params, bank, tracer=Tracer("step"))
    eng2, _ = _run_serve(model, params, bank, tracer=Tracer("step"))
    assert strip_times(eng1.tracer.records()) == \
        strip_times(eng2.tracer.records())


# ------------------------------------------------- tracer unit behaviour
def test_levels_and_noop_singleton():
    assert make_tracer("off") is NOOP
    assert make_tracer(None) is NOOP
    assert make_tracer(0) is NOOP
    assert not NOOP.enabled and NOOP.records() == ()
    t = make_tracer("round")
    t.event("kept")
    t.event("dropped", level=LEVELS["step"])   # above the tracer's level
    assert [r["name"] for r in t.records()] == ["kept"]


def test_span_nesting_depth_and_ring_capacity():
    t = Tracer("step", capacity=4)
    with t.span("outer"):
        with t.span("inner", a=1):
            t.event("leaf")
    recs = t.records()
    # push-at-exit: leaf (depth 2), inner (1), outer (0)
    assert [(r["name"], r["depth"]) for r in recs] == \
        [("leaf", 2), ("inner", 1), ("outer", 0)]
    for i in range(10):
        t.event("spam", i=i)
    assert len(t.records()) == 4      # ring kept the newest
    assert t.dropped == 9
    assert t.records()[-1]["attrs"]["i"] == 9


def test_sim_clock_records():
    t = Tracer("round")
    t.span_at("flight", 1.5, 4.0, lane=3, client=7)
    t.event_at("arrival", 4.0, client=7)
    span, ev = t.records()
    assert span["t_sim"] == 1.5 and span["dur_sim"] == 2.5
    assert span["lane"] == 3
    assert ev["t_sim"] == 4.0


# ------------------------------------------ sharding fallback routing (S1)
def test_fallback_event_exactly_once_per_drain():
    mesh = type("_FakeMesh", (), {"shape": {"data": 2, "model": 4}})()
    params = {"tail": {"head": {"w": jax.ShapeDtypeStruct((32, 10),
                                                          jnp.float32)}}}
    pop_sharding_fallbacks()
    specs = params_pspecs(params, mesh)
    assert specs["tail"]["head"]["w"][1] is None   # 10 % 4 -> replicated
    tracer = Tracer("round")
    with pytest.warns(UserWarning, match=r"(?s)\[unit\].*head/w"):
        entries = rules.report_fallbacks("unit", tracer)
    assert ("tail/head/w", "model", (32, 10)) in entries
    events = [r for r in tracer.records()
              if r["name"] == "sharding.fallback"]
    assert len(events) == 1
    assert events[0]["attrs"]["context"] == "unit"
    assert events[0]["attrs"]["n"] == len(entries)
    assert ["tail/head/w", "model", [32, 10]] in \
        events[0]["attrs"]["entries"]
    # the drain emptied the log: a second report emits NOTHING
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert rules.report_fallbacks("unit", tracer) == ()
    assert len([r for r in tracer.records()
                if r["name"] == "sharding.fallback"]) == 1


def test_fallback_warning_path_survives_untraced():
    rules._SHARDING_FALLBACKS.append(("x/w", "model", (3, 5)))
    with pytest.warns(UserWarning, match=r"(?s)\[site\].*x/w"):
        assert report_sharding_fallbacks("site") != ()


def test_traced_round_build_reports_fallback_once(proto_setup):
    """The protocol's mesh-jit build site drains into ONE structured
    event (context protocol.mesh_jit) when the mesh triggers fallbacks.
    Simulated by seeding the log before the build-site drain."""
    model, clients = proto_setup
    tracer = Tracer("round")
    rules._SHARDING_FALLBACKS.append(("tail/w", "model", (7, 3)))
    with pytest.warns(UserWarning, match=r"(?s)\[protocol\.mesh_jit\]"):
        rules.report_fallbacks("protocol.mesh_jit", tracer)
    events = [r for r in tracer.records()
              if r["name"] == "sharding.fallback"]
    assert len(events) == 1
    assert events[0]["attrs"]["context"] == "protocol.mesh_jit"


# ------------------------------------------------- meter round-trip (S2)
def test_meter_state_dict_roundtrip_including_wall():
    m = TrafficMeter()
    m.absorb({"head_body": 10.0, "body_tail": 3.5, "params": 100.25},
             clients=4)
    m.absorb_wall(server_busy_s=1.5, client_compute_s=7.25, wire_s=2.0,
                  span_s=4.0)
    m2 = TrafficMeter()
    m2.load_state_dict(m.state_dict())
    assert m2.totals == m.totals
    assert m2.wall == m.wall
    assert (m2.rounds, m2.client_rounds) == (m.rounds, m.client_rounds)


def test_meter_legacy_state_without_wall_restores_zeroed():
    m = TrafficMeter()
    m.absorb({"head_body": 8.0})
    state = {k: v for k, v in m.state_dict().items()
             if not k.startswith("wall/")}
    m2 = TrafficMeter()
    m2.absorb_wall(span_s=9.0)   # stale value the restore must clear
    m2.load_state_dict(state)
    assert m2.totals == m.totals
    assert m2.wall == {n: 0.0 for n in WALL_STREAMS}


def test_meter_state_dict_roundtrip_property():
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install -r "
               "requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    finite = st.floats(0.0, 1e12, allow_nan=False, allow_infinity=False)

    @given(byte_rounds=st.lists(
               st.dictionaries(st.sampled_from(
                   ("head_body", "body_tail", "params", "secure",
                    "edge_global", "not_a_stream")), finite, max_size=4),
               max_size=5),
           clients=finite,
           wall=st.lists(st.tuples(finite, finite, finite, finite),
                         max_size=3))
    @settings(max_examples=25, deadline=None)
    def roundtrip(byte_rounds, clients, wall):
        m = TrafficMeter()
        for counts in byte_rounds:
            m.absorb(counts, clients=clients)
        for s, c, w, sp in wall:
            m.absorb_wall(server_busy_s=s, client_compute_s=c, wire_s=w,
                          span_s=sp)
        m2 = TrafficMeter()
        m2.load_state_dict(m.state_dict())
        assert m2.totals == m.totals
        assert m2.wall == m.wall
        assert m2.rounds == m.rounds
        assert m2.client_rounds == m.client_rounds
        assert m2.state_dict() == m.state_dict()

    roundtrip()


def test_meter_absorb_events_match_totals_exactly():
    """Unit-level exactness: the absorb event carries the floats the
    totals folded, unknown streams excluded."""
    tracer = Tracer("round")
    m = TrafficMeter()
    m.attach_tracer(tracer)
    m.absorb({"head_body": 0.1, "params": 0.2, "bogus": 9.9})
    m.absorb({"head_body": 0.3})
    recs = tracer.records()
    assert sum_stream(recs, "meter.absorb", "head_body") == \
        m.totals["head_body"]
    assert all("bogus" not in r["attrs"] for r in recs)
    m.attach_tracer(NOOP)     # disabled tracer detaches
    assert m.tracer is None


# ------------------------------------------------------------- exporters
def _sample_records():
    t = Tracer("step")
    m = TrafficMeter()
    m.attach_tracer(t)
    with t.span("round", cohort=2):
        m.absorb({"head_body": 64.0, "params": 128.0})
    t.span_at("async.client", 0.5, 2.0, lane=4, client=4)
    t.event_at("async.flush", 2.0, version=1)
    return t, m


def test_write_jsonl_appends_meter_final(tmp_path):
    t, m = _sample_records()
    path = str(tmp_path / "trace.jsonl")
    n = write_jsonl(path, t.records(), m)
    lines = [json.loads(line) for line in open(path)]
    assert len(lines) == n == len(t.records()) + 1
    final = lines[-1]
    assert final["name"] == "meter.final"
    assert final["attrs"]["head_body"] == m.totals["head_body"]
    assert final["seq"] == lines[-2]["seq"] + 1


def test_chrome_trace_layout():
    t, m = _sample_records()
    doc = chrome_trace(t.records(), m)
    events = doc["traceEvents"]
    span = next(e for e in events if e["name"] == "round")
    assert span["ph"] == "X" and span["pid"] == 0
    flight = next(e for e in events if e["name"] == "async.client")
    assert flight["ph"] == "X" and flight["pid"] == 1
    assert flight["tid"] == 4
    assert flight["ts"] == 0.5e6 and flight["dur"] == 1.5e6
    lanes = [e for e in events if e.get("name") == "thread_name"]
    assert any(e["args"]["name"] == "lane 4" for e in lanes)
    assert any(e["name"] == "meter.final" and e["ph"] == "i"
               for e in events)


def test_prometheus_text_sanitizes_and_skips_nonnumeric():
    reg = MetricsRegistry()
    reg.counter("tokens_out").inc(5, labels={"tenant": "1"})
    reg.register_source("meter", lambda: {"totals/head_body": 2.5,
                                          "note": "text-skipped"})
    text = prometheus_text(reg.snapshot())
    assert 'tokens_out{tenant="1"} 5.0' in text
    assert "meter_totals_head_body 2.5" in text
    assert "note" not in text


def test_registry_instruments_and_sources():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    assert reg.counter("hits") is c            # idempotent by name
    with pytest.raises(ValueError):
        reg.gauge("hits")                      # cross-kind clash
    c.inc(2)
    g = reg.gauge("fill")
    g.set_fn(lambda: 0.75)
    h = reg.histogram("lat", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()
    assert snap["hits"] == 2.0
    assert snap["fill"] == 0.75
    assert snap['lat_bucket{le="1.0"}'] == 1
    assert snap['lat_bucket{le="+Inf"}'] == 2
    assert snap["lat_count"] == 2


def test_registry_binds_live_engine(serve_setup):
    model, params, bank = serve_setup
    eng, stats = _run_serve(model, params, bank)
    reg = MetricsRegistry()
    reg.bind_engine(eng)
    reg.bind_pool(eng.pool_alloc)
    snap = reg.snapshot()
    assert snap["serve/tokens_out"] == stats["tokens_out"]
    assert snap["serve/wire_bytes/total"] == stats["wire_bytes"]["total"]
    assert snap["pages/n_pages"] == eng.pool_alloc.n_pages
    assert snap["pages/n_used"] == eng.pool_alloc.n_used


# ------------------------------------------------------- trace_check (S5)
@pytest.fixture(scope="module")
def trace_check():
    path = os.path.join(REPO, "tools", "trace_check.py")
    spec = importlib.util.spec_from_file_location("trace_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_str(trace_check, text):
    return trace_check.check(io.StringIO(text))


def test_trace_check_accepts_real_export(trace_check, tmp_path,
                                         proto_setup):
    model, clients = proto_setup
    tr, _ = _run_rounds(model, clients, tracer=Tracer("step"))
    path = str(tmp_path / "run.jsonl")
    write_jsonl(path, tr.tracer.records(), tr.meter)
    with open(path) as f:
        assert trace_check.check(f) == 0


def test_trace_check_rejects_byte_drift(trace_check):
    recs = [
        {"seq": 0, "kind": "event", "name": "meter.absorb", "depth": 0,
         "t_ns": 1, "attrs": {"head_body": 4.0}},
        {"seq": 1, "kind": "event", "name": "meter.final", "depth": 0,
         "attrs": {"head_body": 5.0, "rounds": 1}},
    ]
    text = "".join(json.dumps(r) + "\n" for r in recs)
    assert _check_str(trace_check, text) == 1


def test_trace_check_rejects_schema_violations(trace_check):
    bad = [
        '{"kind": "span", "name": "x", "seq": 0, "depth": 0}\n',   # no dur
        '{"kind": "what", "name": "x", "seq": 0, "depth": 0, '
        '"t_ns": 1, "attrs": {}}\n',                               # bad kind
        'not json\n',
    ]
    for text in bad:
        assert _check_str(trace_check, text) == 1
    # out-of-order seq
    ok = {"seq": 5, "kind": "event", "name": "e", "depth": 0, "t_ns": 1,
          "attrs": {}}
    text = json.dumps(ok) + "\n" + json.dumps(dict(ok, seq=4)) + "\n"
    assert _check_str(trace_check, text) == 1
    assert _check_str(trace_check, json.dumps(ok) + "\n") == 0


def test_trace_check_empty_is_failure(trace_check):
    assert _check_str(trace_check, "") == 1
