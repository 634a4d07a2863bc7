"""The benchmark's yardstick on the CPU: the trace reduction, the metric
readers with their FLOP and byte arithmetic, the population generator,
that every cell resolves its files by name, and that a run refuses a
machine without a TPU."""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench import common, trace, traffic

BENCH = common.benchmark()


# ----------------------------------------------------------------- trace
def _synthetic():
    ms = 1e6   # ns
    devices = {"/device:TPU:0": [
        (1 * ms, 3 * ms, "%fusion.1 = f32[8] fusion(f32[8] %p)"),
        (2 * ms, 4 * ms, "%fusion.2 = f32[8] fusion(f32[8] %p)"),
        (6 * ms, 7 * ms, "%sfprompt_secure_masked_encode.3 = u32[4] "
                         "custom-call(f32[4] %q), custom_call_target="
                         "\"tpu_custom_call\""),
        (7 * ms, 8 * ms, "%copy.9 = u32[4] copy(u32[4] "
                         "%sfprompt_secure_masked_encode.3)"),
        (11 * ms, 12 * ms, "%fusion.1 = f32[8] fusion(f32[8] %p)"),
    ]}
    host = [(0, 10 * ms, trace.WINDOW_SPAN),
            (0, 10 * ms, "bench.round"),
            (4 * ms, 6 * ms, "bench.gather"),
            (12 * ms, 13 * ms, "bench.round")]
    return devices, host


def test_trace_reduction_on_a_synthetic_trace():
    red = trace.reduce(*_synthetic())
    # busy: [1, 4] U [6, 8] inside the 10 ms window; [11, 12] is outside
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.005)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.5)
    # the kernel's own op only, not the copy that reads its result
    assert red["op_s"]["sfprompt_secure_masked_encode"] == \
        pytest.approx(0.001)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.002)
    assert ops["copy.9"] == pytest.approx(0.001)
    # gaps [0,1] and [8,10] fall under bench.round, [4,6] under the gather
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["bench.round"] == pytest.approx(0.003)
    assert gaps["bench.gather"] == pytest.approx(0.002)


def test_merge_and_clip():
    assert trace.merge([(5, 6), (1, 3), (2, 4), (4, 4)]) == [(1, 4), (5, 6)]
    assert trace.clip([(0, 5), (8, 9)], 2, 8.5) == [(2, 5), (8, 8.5)]
    assert trace.op_name("%fusion.12 = f32[2] fusion()") == "fusion.12"


def _ctx(cell, cohorts):
    spec = common.resolve(cell)
    return {"trace": trace.reduce(*_synthetic()),
            "peaks": common.peaks("TPU v5 lite"), "config": spec["config"],
            "traffic": spec["traffic"],
            "window": {"rounds": len(cohorts), "cohorts": cohorts,
                       "client_sizes": [32] * 30}}


def test_metric_readers_on_a_synthetic_trace():
    peaks = common.peaks("TPU v5 lite")
    ctx = _ctx("vitb-fed-u1-secure", [[0, 1, 2], [3, 4, 5]])
    assert common.metric_reader("idle_share.round")(ctx) == \
        pytest.approx(50.0)
    mfu = common.load_module(common.BENCH / "metrics" / "mfu.round.py")
    flops = 6 * mfu.client_flops(ctx["config"], ctx["traffic"])
    assert common.metric_reader("mfu.round")(ctx) == pytest.approx(
        100 * flops / (0.010 * peaks["bf16_flops_per_s"]))
    # 6 clients' 7,171,584 values, 4 bytes in and 4 out, in the kernel's
    # 1 ms of device time
    assert common.metric_reader("secure_mask_roofline")(ctx) == \
        pytest.approx(100 * 6 * 8 * 7_171_584 / peaks["hbm_bytes_per_s"]
                      / 0.001)


def test_metric_readers_with_nothing_to_read():
    # no rounds in the window, or a clear cell: no number rather than 0
    assert common.metric_reader("mfu.round")(_ctx("vitb-fed-u10", [])) is None
    for cell, cohorts in (("vitb-fed-u10", [[0, 1, 2]]),
                          ("vitb-fed-u1-secure", [])):
        ctx = _ctx(cell, cohorts)
        assert common.metric_reader("secure_mask_roofline")(ctx) is None


def test_trainable_values_of_vit_base():
    # tail layer 4*768^2 + 2*768*3072 + 4*768, final norm 2*768,
    # classifier 768*100, prompt 16*768
    sm = common.load_module(common.BENCH / "metrics"
                            / "secure_mask_roofline.py")
    cfg = common.resolve("vitb-fed-u10")["config"]
    assert sm.trainable_values(cfg) == 7_080_960 + 1_536 + 76_800 + 12_288


def test_population_same_sizes_for_every_seed():
    mix = {"clients": 5, "samples_per_client": 4, "client_sizes": [4, 2, 1]}
    rows_a, sizes_a = traffic.population(mix, 1)
    rows_b, sizes_b = traffic.population(mix, 2**33 + 1)
    assert sorted(sizes_a) == sorted(sizes_b) == [1, 2, 2, 4, 4]
    assert list(sizes_a) != list(sizes_b)
    for rows, sizes in ((rows_a, sizes_a), (rows_b, sizes_b)):
        assert rows.shape == (5, 4)
        start = 0
        for r, n in zip(rows, sizes):
            # a client's own block of samples, repeated in order
            assert list(r) == [start + i % n for i in range(4)]
            start += n
        assert start == sizes.sum()


def test_population_refuses_sizes_past_its_rows():
    with pytest.raises(ValueError):
        traffic.population({"clients": 2, "samples_per_client": 4,
                            "client_sizes": [5]}, 0)


# ------------------------------------------------------------------ FLOPs
def test_vit_flops_by_hand():
    mfu = common.load_module(common.BENCH / "metrics" / "mfu.round.py")
    # T=3 tokens, D=2, F=4: 2*3*(4*4 + 2*2*4) + 4*9*2 = 192 + 72
    assert mfu.layer_forward(3, 2, 4) == 264.0
    # image 2, patch 1, 1 channel: N=4 patches of P=1; prompt 2: T=7
    cfg = {"hidden_size": 2, "intermediate_size": 4, "image_size": 2,
           "patch_size": 1, "num_channels": 1, "num_labels": 7,
           "split": {"head_layers": 1, "body_layers": 2, "tail_layers": 1,
                     "prompt_len": 2, "prune_gamma": 0.5}}
    mix = {"samples_per_client": 8, "batch": 4, "local_epochs": 10}
    layer = mfu.layer_forward(7, 2, 4)
    embed, cls = 2 * 4 * 1 * 2, 2 * 2 * 7
    local_train = embed + 2 * layer + 3 * (layer + cls)
    local_fwd = embed + 2 * layer + cls
    split_train = embed + 2 * 3 * layer + 3 * (layer + cls)
    # gamma 0.5 keeps 4 of 8
    assert mfu.client_flops(cfg, mix) == \
        10 * 8 * local_train + 8 * local_fwd + 4 * split_train


@pytest.mark.parametrize("traced", [False, True])
def test_result_line(traced):
    """bench/run.py's last line from a system's record of a run: the
    cell's end-to-end or per-layer metrics, the device, and the checks as
    the last key."""
    run = common.load_module(common.BENCH / "run.py")
    spec = common.resolve("vitb-fed-u1-secure")
    out = {"setup_s": 20.5, "end_to_end": {"round_s": 0.25},
           "attempted": 40, "failed": 0,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 12},
           "trace": trace.reduce(*_synthetic()),
           "window": {"rounds": 1, "cohorts": [[0, 1, 2]],
                      "client_sizes": [32] * 30},
           "checks": [{"name": "loss", "value": 1e-4, "limit": 1e-3},
                      {"name": "wire_bytes", "value": 0.0, "limit": 0}]}
    line = run.result_line(spec, out, trace=traced,
                           peaks=common.peaks("TPU v5 lite"))
    assert line["correct"] and list(line)[-1] == "checks"
    assert line["checks"]["loss"] == {"value": 1e-4, "limit": 1e-3}
    if traced:
        assert set(line["metrics"]) == {"idle_share.round", "mfu.round",
                                        "secure_mask_roofline"}
        assert line["device"]["busy_s"] == pytest.approx(0.005)
        assert line["device"]["window_s"] == pytest.approx(0.010)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert line["metrics"] == {"setup_s": {"value": 20.5, "unit": "s"},
                                   "round_s": {"value": 0.25, "unit": "s"}}
    out["checks"][0]["value"] = 2e-3
    assert not run.result_line(spec, out, trace=traced,
                               peaks=common.peaks("TPU v5 lite"))["correct"]


def test_memory_peak_takes_the_reserved_temporaries():
    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, used, reserved):
            self.stats = {"peak_bytes_in_use": used,
                          "peak_bytes_reserved": reserved}

        def memory_stats(self):
            return self.stats
    rec = common.device_record([Chip(9, 120), Chip(100, 50)])
    assert rec == {"platform": "tpu", "kind": "TPU v5 lite", "count": 2,
                   "memory_peak_bytes": 120}


# ----------------------------------------------------------- the contract
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves_by_name():
    names = [m["name"] for m in BENCH["per_layer"]]
    for m in names:
        assert (common.BENCH / "metrics" / f"{m}.py").is_file(), m
        assert callable(common.metric_reader(m))
    for w in BENCH["workloads"]:
        spec = common.resolve(w["name"])
        assert spec["system"].is_file() and spec["reference"].is_file()
        assert (common.BENCH / "limits" / f"{w['name']}.json").is_file()
        ref = common.load_module(spec["reference"])
        for fn in ("make_weights", "to_program", "sizes"):
            assert callable(getattr(ref, fn))
        e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert spec["per_layer"], w["name"]
        for m in spec["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_benchmark_json_keeps_the_contract():
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for item in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(item["name"]), item["name"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["unit"] == "%" and "bound" not in m
        layers.setdefault(m["layer"], set()).add(m["name"])
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(set(cells)) == len(cells)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= len(cells) // 2
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


# --------------------------------------------------------------- no chip
def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(common.BENCH / "run.py"), "--workload",
         "vitb-fed-u10", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=str(common.ROOT),
        timeout=120)
    assert res.returncode == 2
    assert res.stdout.strip() == ""
    assert "TPU" in res.stderr
