"""Split serving in the benchmark at a size the CPU holds: the plain
reference against the program's paged engine, `correct` for a sound run
and for each fault the timed path can have, the bfloat16 control, the
closed-loop generator, the decode record read from the flight recorder,
and the metric readers' byte and FLOP counts at the published shapes.
The harness's look for a chip is skipped; everything else runs as in a
run of the cell."""
import time

import jax
import numpy as np
import pytest

import tiny
from bench import common, trace, traffic
from bench.systems import serving
from repro.obs import NOOP, make_tracer

CELL = "q14b-decode-closed"
REF = common.load_module(common.BENCH / "configs" / "qwen2.5-14b.py")
PUBLISHED = common.resolve(CELL)["config"]
PEAKS = common.peaks("TPU v5 lite")


def _run(seed=7, plant=None, seconds=0.6):
    return common.Run(config=tiny.QWEN, traffic=tiny.CLOSED, ref=REF,
                      limits=tiny.limits(CELL), seed=seed, seconds=seconds,
                      devices=jax.devices()[:1], clock=common.CompileClock(),
                      t_start=time.time(), plant=plant)


# ---------------------------------------------------------------- reference
def test_reference_matches_the_paged_engine():
    """Prefill, then paged decode through the cache, against the
    reference's full forward teacher-forced on the served tokens. On the
    CPU both compute in float32, in different orders of summation: the
    logits agree to 1e-5 of the largest, where bfloat16 rounding of one
    matmul input alone would leave ~4e-3."""
    r = _run(seed=3)
    w = serving.make_weights(r)
    engine = serving.build(r, REF.to_program(w, r.config), NOOP)
    checks = serving.serve_checks(r, engine)
    assert len(checks) == tiny.QWEN["serve"]["n_slots"] + 1
    with jax.default_matmul_precision("highest"):
        for f in checks:
            toks = np.concatenate([f.req.tokens, f.tokens[:-1]])
            logits = np.asarray(REF.forward(w, toks, r.config))
            p = len(f.req.tokens) + r.config["split"]["prompt_len"]
            want = logits[p - 1:]
            assert want.shape == f.logits.shape
            assert np.abs(f.logits - want).max() <= \
                1e-5 * np.abs(want).max()
            assert (f.tokens == want.argmax(-1)).all()


def test_weights_are_the_program_layout_without_a_copy():
    """`to_program` regroups the reference's arrays into `SplitModel`'s
    head and body and the tenant bank's stacked tails: every leaf is the
    same object, with the shape the program would make itself."""
    from repro.core.split import SplitModel
    cfg = tiny.QWEN
    w = jax.jit(lambda k: REF.make_weights(cfg, k))(jax.random.PRNGKey(0))
    prog = REF.to_program(w, cfg)
    ids = {id(x) for x in jax.tree.leaves(w)}
    assert {id(x) for x in jax.tree.leaves(prog)} == ids
    model = SplitModel(*common.program_model(cfg))
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)
    assert shape(prog["shared"]) == shape({"head": init["head"],
                                           "body": init["body"]})
    assert shape(prog["tails"]) == shape(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype),
        init["tail"]))
    assert prog["prompts"].shape == (1,) + init["prompt"].shape


@pytest.mark.parametrize("cell,layers", [("q14b-decode-closed", 3),
                                         ("vitb-fed-u10", None)])
def test_model_config_is_the_launchers_at_published_sizes(cell, layers):
    """Each cell's model is built at the shapes its launcher runs:
    `launch/serve.py --no-reduced --layers 3` serves qwen2.5-14b, and
    `launch/train.py` without `--reduced` trains the published vit-base,
    the registry's config that `served_config` returns with no cut."""
    from repro.launch.serve import served_config
    config = common.resolve(cell)["config"]
    mine, _ = common.program_model(config)
    theirs = served_config(config["program"]["arch"], reduced=False,
                           layers=layers)
    for field in ("arch_type", "n_layers", "d_model", "d_ff", "vocab_size",
                  "layer_pattern", "attention", "mlp_activation", "norm",
                  "tie_embeddings", "final_logit_softcap", "n_dense_layers",
                  "num_classes", "encoder", "moe"):
        assert getattr(mine, field) == getattr(theirs, field), field


# ------------------------------------------------------------------ correct
@pytest.mark.parametrize("fault", [None] + sorted(serving.FAULTS))
def test_serving_faults_fail(fault):
    plant = serving.FAULTS[fault] if fault else None
    out = tiny.run(tiny.QWEN, tiny.CLOSED, "qwen2.5-14b", CELL,
                   plant=plant)
    assert tiny.correct(out) == (fault is None), out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["tokens_per_s"] > 0


def test_serving_control_reads_far_above_the_program():
    """The reference computed in bfloat16, in the program's place, on the
    same requests. On the CPU the program and the reference both compute
    exact float32 products, so the program reads ~1e-7 and the control
    the bfloat16 rounding (~1e-2 at this size); on the chip, at the
    cell's size, the control fails the limits (PERF.md, section 4)."""
    r = _run(seed=11)
    got = dict(serving.calibration(r))
    limits = tiny.limits(CELL)
    assert all(got["program"][k] <= limits[k]["limit"]
               for k in serving.COMPARED), got
    assert got["control"]["logits"] > 1000 * got["program"]["logits"], got


# ------------------------------------------------------------------ traffic
def test_closed_loop_is_pure_in_the_seed_and_keeps_its_sets():
    mix = dict(tiny.CLOSED, callers=3)
    take = lambda seed: [[(t.tolist(), n) for t, n in
                          (next(s) for _ in range(12))]
                         for s in traffic.closed_loop(mix, seed, 256)]
    a, b = take(2**33 + 5), take(2**33 + 5)
    assert a == b != take(2**33 + 6)
    pairs = {(p, n) for p in mix["prompt_lengths"]
             for n in mix["output_lengths"]}
    for caller in a:
        # every block of len(pairs) requests holds each pair once
        for i in range(0, 12 - len(pairs) + 1, len(pairs)):
            assert {(len(t), n) for t, n in caller[i:i + len(pairs)]} \
                == pairs
        assert all(0 <= x < 256 for t, _ in caller for x in t)
    checks = traffic.check_requests(mix, 1, 256, 4)
    assert [(len(t), n) for t, n in checks] == [(8, 6), (4, 3), (8, 6),
                                                (4, 3)]


def test_one_request_in_flight_per_caller():
    """The window's loop sends a caller's next request only after its
    previous one has finished."""
    log = []

    def watch(engine):
        submit, step = engine.submit, engine.step

        def sub(req):
            log.append(("sent", req.rid))
            return submit(req)

        def stp():
            done = step()
            log.extend(("done", f.req.rid) for f in done)
            return done
        engine.submit, engine.step = sub, stp
    r = _run(seed=5, plant=watch)
    s = serving.serve(r, serving.make_weights(r), NOOP)
    owner = {rid: c for rid, (c, _, _) in s["sent"].items()}
    busy = {}
    for what, rid in log:
        if rid not in owner:          # a check request
            continue
        c = owner[rid]
        if what == "sent":
            assert c not in busy, (c, busy[c], rid)
            busy[c] = rid
        else:
            assert busy.pop(c) == rid
    assert len(s["sent"]) > tiny.CLOSED["callers"]


# ------------------------------------------------------------ decode record
def test_decode_record_from_the_flight_recorder():
    """Two requests admitted (prompts 8 and 4, outputs 3 and 6), one
    dispatch of 4 iterations, the first retires, one more of 1."""
    sent = {10: (0, 8, 3), 11: (1, 4, 6)}
    ev = lambda seq, name, **a: {"seq": seq, "name": name, "attrs": a}
    recs = [ev(0, "serve.admit", rid=10, slot=3, tenant=0),
            ev(1, "serve.admit", rid=11, slot=2, tenant=0),
            ev(2, "serve.decode", n_tokens=4, active=2, step=0),
            ev(3, "serve.retire", rid=10, tenant=0, n_tokens=3),
            ev(4, "serve.decode", n_tokens=1, active=1, step=4)]
    rec = serving.decode_record(recs, sent, prompt_len=4)
    # slot 3 holds 4+8+1 positions at its first decode and owes 2 tokens;
    # slot 2 holds 4+4+1 and owes 5
    assert rec["iterations"] == [[2, 1, 13 + 9], [2, 1, 14 + 10],
                                 [1, 1, 11], [1, 1, 12], [1, 1, 13]]
    assert rec["prefills"] == [12, 8] and rec["active_mismatches"] == 0


def test_flight_recorder_record_of_a_window():
    """The record a traced run takes, from a live recorder at level
    "step": the decode tokens it counts equal the engine's."""
    r = _run(seed=9)
    s = serving.serve(r, serving.make_weights(r), make_tracer("step"))
    rec = s["record"]
    assert rec["active_mismatches"] == 0 and rec["iterations"]
    decoded = sum(a for a, _, _ in rec["iterations"])
    assert decoded + len(rec["prefills"]) == s["tokens"]
    assert s["compiles"] == 0


# ------------------------------------------------------------------ readers
def test_decode_bytes_and_flops_by_hand_at_published_shapes():
    mbu = common.load_module(common.BENCH / "metrics" / "mbu.decode.py")
    mfu = common.load_module(common.BENCH / "metrics" / "mfu.decode.py")
    att = common.load_module(common.BENCH / "metrics"
                             / "paged_decode_attn_roofline.py")
    # q, o 5120x5120; k, v 5120x1024; biases 5120+1024+1024; SwiGLU
    # 3x5120x13824; two norms
    layer = 2 * 5120 ** 2 + 2 * 5120 * 1024 + 7168 + 3 * 5120 * 13824 \
        + 2 * 5120
    assert mbu.layer_values(PUBLISHED) == layer == 275_268_608
    # three layers, one tenant's final norm and 5120x152064 output layer,
    # one embedding row: 6.42 GB an iteration
    weights = 4 * (3 * layer + 5120 + 5120 * 152_064)
    assert weights == 6_417_514_496
    assert mbu.iteration_bytes(PUBLISHED, 1, 1, 0) == weights + 4 * 5120
    # K and V of 8 kv heads of 128 per position and layer, f32
    assert mbu.iteration_bytes(PUBLISHED, 4, 1, 1000) == \
        weights + 4 * 4 * 5120 + 1000 * 3 * 2 * 1024 * 4
    assert att.call_bytes(PUBLISHED, 4, 1000) == \
        1000 * 2 * 1024 * 4 + 4 * 2 * 5120 * 4
    matmul = layer - 7168 - 2 * 5120
    assert mfu.iteration_flops(PUBLISHED, 4, 1000) == \
        2 * 4 * (3 * matmul + 5120 * 152_064) + 4 * 5120 * 3 * 1000


def _ctx(iterations, kernel=(0.0, 0)):
    red = {"busy_s": 0.09, "window_s": 0.1, "op_s": {}, "op_n": {},
           "breakdown": {"device_ops": [], "idle_gaps": []}}
    if kernel[1]:
        red["op_s"][trace.base_name("sfprompt_paged_decode_attention.2")] \
            = kernel[0]
        red["op_n"]["sfprompt_paged_decode_attention"] = kernel[1]
    return {"trace": red, "peaks": PEAKS, "config": PUBLISHED,
            "traffic": common.resolve(CELL)["traffic"],
            "window": {"iterations": iterations, "prefills": []}}


def test_serving_readers_on_a_record():
    its = [[4, 1, 1200], [3, 1, 900]]
    ctx = _ctx(its, kernel=(0.001, 4))
    mbu = common.load_module(common.BENCH / "metrics" / "mbu.decode.py")
    att = common.load_module(common.BENCH / "metrics"
                             / "paged_decode_attn_roofline.py")
    need = sum(mbu.iteration_bytes(PUBLISHED, a, n, p) for a, n, p in its)
    assert common.metric_reader("mbu.decode")(ctx) == pytest.approx(
        100 * need / (0.1 * PEAKS["hbm_bytes_per_s"]))
    assert common.metric_reader("idle_share.decode")(ctx) == \
        pytest.approx(10.0)
    # 4 kernel calls over 2 iterations: two layers a step
    per = 2 * sum(att.call_bytes(PUBLISHED, a, p) for a, _, p in its)
    assert common.metric_reader("paged_decode_attn_roofline")(ctx) == \
        pytest.approx(100 * per / PEAKS["hbm_bytes_per_s"] / 0.001)
    assert 0 < common.metric_reader("mfu.decode")(ctx) < 100


@pytest.mark.parametrize("name", ["mbu.decode", "mfu.decode",
                                  "paged_decode_attn_roofline",
                                  "idle_share.decode"])
def test_serving_readers_with_nothing_to_read(name):
    """No decode iteration, no kernel in the trace, or no trace: no
    number rather than 0."""
    read = common.metric_reader(name)
    assert read(dict(_ctx([]), trace=None)) is None
    if name != "idle_share.decode":
        assert read(_ctx([])) is None
    if name == "paged_decode_attn_roofline":
        assert read(_ctx([[4, 1, 100]])) is None


def test_trace_counts_calls_by_op_name():
    ms = 1e6
    devices = {"/device:TPU:0": [
        (1 * ms, 2 * ms, "%sfprompt_paged_decode_attention.1 = f32[4] "
                         "custom-call(f32[4] %q)"),
        (3 * ms, 4 * ms, "%sfprompt_paged_decode_attention.7 = f32[4] "
                         "custom-call(f32[4] %q)"),
        (12 * ms, 13 * ms, "%sfprompt_paged_decode_attention.1 = f32[4] "
                           "custom-call(f32[4] %q)")]}
    red = trace.reduce(devices, [(0, 10 * ms, trace.WINDOW_SPAN)])
    assert red["op_n"]["sfprompt_paged_decode_attention"] == 2
    assert red["op_s"]["sfprompt_paged_decode_attention"] == \
        pytest.approx(0.002)


@pytest.mark.parametrize("traced", [False, True])
def test_serving_result_line(traced):
    run = common.load_module(common.BENCH / "run.py")
    spec = common.resolve(CELL)
    out = {"setup_s": 40.0, "end_to_end": {"tokens_per_s": 170.0},
           "attempted": 20, "failed": 0,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 15},
           "trace": _ctx([])["trace"],
           "window": {"iterations": [[4, 1, 1200]], "prefills": [36]},
           "checks": [{"name": "logits", "value": 1e-3, "limit": 1e-2}]}
    out["trace"]["op_s"]["sfprompt_paged_decode_attention"] = 0.001
    out["trace"]["op_n"]["sfprompt_paged_decode_attention"] = 2
    line = run.result_line(spec, out, trace=traced, peaks=PEAKS)
    assert line["correct"] and list(line)[-1] == "checks"
    if traced:
        assert set(line["metrics"]) == {"idle_share.decode", "mbu.decode",
                                        "mfu.decode",
                                        "paged_decode_attn_roofline"}
    else:
        assert line["metrics"] == {
            "setup_s": {"value": 40.0, "unit": "s"},
            "tokens_per_s": {"value": 170.0, "unit": "tokens/s"}}
