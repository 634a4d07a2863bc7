"""Run one cell of the benchmark once, on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json; its configuration,
traffic mix, system module, limits and per-layer metric readers are found
by name under bench/ (see bench/common.py). Set-up makes the weights and
the data from the seed and warms every shape the window uses; the window
then measures for `--seconds` seconds. With `--trace 0` the result carries
the cell's end-to-end metrics; with `--trace 1` a profiler trace of the
window gives its per-layer metrics (a traced window lasts at most 10 s).

After the window the timed path's output is compared with the plain
reference; each number compared is printed beside its limit as the last
lines on standard error and, under `checks`, as the last key of the
result. The last line of standard output is the result, one JSON object.

Without a TPU, or with fewer chips than the cell asks for, it prints why
on standard error and exits 2 with no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import common  # noqa: E402


def result_line(spec, out, *, trace: bool, peaks) -> dict:
    """The last line of standard output."""
    checks = out["checks"]
    correct = bool(checks) and all(c["value"] <= c["limit"] for c in checks)
    device = dict(out["device"])
    metrics = {}
    if trace:
        red = out["trace"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        ctx = {"trace": red, "peaks": peaks, "config": spec["config"],
               "traffic": spec["traffic"], "window": out["window"]}
        for m in spec["per_layer"]:
            value = common.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = out["trace"]["breakdown"]
    line["checks"] = common.check_line(checks)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = common.resolve(args.workload)
    cache = common.prepare_environment()
    try:
        devices = common.require_chips(spec["cell"]["chips"])
        peaks = common.peaks(devices[0].device_kind)
    except common.NoDevice as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    clock = common.CompileClock()
    run = common.Run(
        config=spec["config"], traffic=spec["traffic"],
        ref=common.load_module(spec["reference"]),
        limits=common.load_json(common.BENCH / "limits"
                                / f"{args.workload}.json"),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, clock=clock, t_start=T_START)
    run.mark("devices")
    out = common.load_module(spec["system"]).run(run)

    print(json.dumps({"setup": {
        "setup_s": out["setup_s"], "compile_s": out["compile_s"],
        "compiles": clock.compiles, "cache_hits": clock.cache_hits,
        "done_at_s": run.marks, "cache_dir": cache}}), flush=True)
    for note in out.get("notes", []):
        print(note, flush=True)
    line = result_line(spec, out, trace=bool(args.trace), peaks=peaks)
    for c in out["checks"]:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
