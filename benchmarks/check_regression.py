"""Benchmark-regression gate: fail CI when a kernel slows down >25%.

Compares the bench job's results JSONs (kernel microbench, serve
throughput, decode fast path — written by `REPRO_BENCH_FAST=1 python
benchmarks/run.py --only kernel_microbench --only serve --only decode`)
against the committed baseline `BENCH_kernels.json` at the repo root.

Two metric classes:
  * ratio metrics ("...speedup") — machine-independent (fused vs naive on
    the SAME host), so they gate by default: a speedup shrinking below
    (1 - threshold) x baseline fails.
  * absolute metrics ("..._us") — meaningful only on a pinned runner, so
    they gate only under --strict; on shared CI runners the jitter and
    hardware drift would make them pure noise.

The baseline carries two deliberate overrides next to the measured
"kernels" numbers, both preserved verbatim across `--update`:
  * "pins" — conservative drift-gate baselines for volatile ratios (the
    reference machine measures e.g. blocked ~4.7x, but shared CI hosts
    jitter, so the gate anchors on a pinned 2.0 instead of chasing the
    measurement). Pins OVERLAY the measured value at check time; the
    "kernels" section always records what the benchmark actually measured.
  * "floors" — HARD minimums on ratio metrics, enforced verbatim (never
    scaled by the threshold): e.g. `attention_2k/blocked_speedup >= 1.0`
    (the flash-style path must never be slower than the naive reference
    again) and `decode_scan/scan_speedup >= 2.0` (the multi-token scan
    must amortize at least 2x of the per-token dispatch cost). A drifting
    baseline can never re-bless a slowdown past its floor.

A kernel present in the results but absent from the baseline (or vice
versa) is SKIPPED with a note, never failed — new kernels get a baseline
via `--update`, which rewrites BENCH_kernels.json from the current results
(run it on the reference machine, commit the diff; pins and floors are
preserved).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(ROOT, "BENCH_kernels.json")
# every results file that can contribute (ref_us, <impl>_us) ratio pairs;
# missing files are skipped so partial bench runs still gate what they ran
DEFAULT_RESULTS = [
    os.path.join(ROOT, "benchmarks", "results", "kernel_microbench.json"),
    os.path.join(ROOT, "benchmarks", "results", "serve_throughput.json"),
    os.path.join(ROOT, "benchmarks", "results", "decode_throughput.json"),
    os.path.join(ROOT, "benchmarks", "results", "serve_paged.json"),
    os.path.join(ROOT, "benchmarks", "results", "secure_agg.json"),
    os.path.join(ROOT, "benchmarks", "results", "population_scale.json"),
    os.path.join(ROOT, "benchmarks", "results", "async_rounds.json"),
    os.path.join(ROOT, "benchmarks", "results", "mesh_tp.json"),
]


def flatten(results: Dict) -> Dict[str, float]:
    """kernel_microbench.json -> flat {kernel/metric: value}, plus derived
    speedup ratios for every (ref_us, <impl>_us) pair so the gate has a
    machine-independent number per kernel."""
    flat: Dict[str, float] = {}
    for kernel, metrics in results.items():
        if not isinstance(metrics, dict):
            continue
        for metric, value in metrics.items():
            if isinstance(value, (int, float)):
                flat[f"{kernel}/{metric}"] = float(value)
        ref = metrics.get("ref_us")
        if isinstance(ref, (int, float)):
            for metric, value in metrics.items():
                if (metric.endswith("_us") and metric != "ref_us"
                        and isinstance(value, (int, float)) and value > 0):
                    name = metric[: -len("_us")]
                    flat[f"{kernel}/{name}_speedup"] = float(ref) / value
    return flat


def check(baseline: Dict[str, float], current: Dict[str, float], *,
          threshold: float, strict: bool,
          floors: Dict[str, float] = None) -> int:
    failures, checked, skipped = [], 0, []
    floors = floors or {}
    for key, base in sorted(baseline.items()):
        if key not in current:
            skipped.append(f"{key} (no measurement this run)")
            continue
        cur = current[key]
        is_ratio = key.endswith("speedup")
        if not is_ratio and not (strict and key.endswith("_us")):
            # absolute wall times gate only on pinned runners; other
            # absolutes (clients_per_sec, bytes_per_round, shape counters)
            # have no slower-is-worse ceiling semantics — floors cover them
            continue
        checked += 1
        if is_ratio:
            floor = base * (1.0 - threshold)
            ok = cur >= floor
            detail = (f"{key}: {cur:.3f}x vs baseline {base:.3f}x "
                      f"(floor {floor:.3f}x)")
        else:
            ceil = base * (1.0 + threshold)
            ok = cur <= ceil
            detail = (f"{key}: {cur:.1f}us vs baseline {base:.1f}us "
                      f"(ceiling {ceil:.1f}us)")
        print(("ok   " if ok else "FAIL ") + detail)
        if not ok:
            failures.append(key)
    # hard floors: absolute minimums on ratio metrics, never threshold-scaled
    for key, floor in sorted(floors.items()):
        if key not in current:
            skipped.append(f"{key} (floor set, no measurement this run)")
            continue
        cur = current[key]
        ok = cur >= floor
        checked += 1
        print(("ok   " if ok else "FAIL ")
              + f"{key}: {cur:.3f}x vs HARD floor {floor:.3f}x")
        if not ok:
            failures.append(f"{key} (hard floor)")
    for key in sorted(set(current) - set(baseline)):
        if key.endswith("speedup"):
            skipped.append(f"{key} (no baseline — run --update to add)")
    for note in skipped:
        print(f"skip {note}")
    if failures:
        print(f"REGRESSION: {len(failures)} kernel metric(s) degraded "
              f">{threshold:.0%} or below a hard floor: "
              f"{failures}")
        return 1
    print(f"OK: {checked} kernel metric(s) within {threshold:.0%} "
          f"of baseline")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--results", action="append", default=None,
                    help="results JSON (repeatable; default: kernel "
                         "microbench + serve throughput)")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="allowed fractional slowdown (default 0.25)")
    ap.add_argument("--strict", action="store_true",
                    help="also gate absolute _us wall times (pinned "
                         "runners only)")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the baseline from the current results "
                         "(hand-set pins and floors are preserved)")
    ap.add_argument("--update-pins", action="store_true",
                    help="with --update: refresh each existing pin to the "
                         "currently measured value instead of preserving "
                         "it — a deliberate re-anchoring, run on the "
                         "reference machine only")
    args = ap.parse_args(argv)
    if args.update_pins and not args.update:
        ap.error("--update-pins only makes sense with --update")

    results_paths = args.results or DEFAULT_RESULTS
    current: Dict[str, float] = {}
    sources = []
    for path in results_paths:
        if not os.path.exists(path):
            print(f"skip: no benchmark results at {path}")
            continue
        with open(path) as f:
            current.update(flatten(json.load(f)))
        sources.append(os.path.relpath(path, ROOT))
    if not sources:
        print("skip: no benchmark results found "
              "(run benchmarks/run.py --only kernel_microbench first)")
        return 0

    prior_floors: Dict[str, float] = {}
    prior_pins: Dict[str, float] = {}
    if os.path.exists(args.baseline):
        with open(args.baseline) as f:
            prior = json.load(f)
        prior_floors = prior.get("floors", {})
        prior_pins = prior.get("pins", {})

    if args.update:
        pins = prior_pins
        pins_note = f"{len(prior_pins)} pins preserved"
        if args.update_pins:
            pins = {k: current.get(k, v) for k, v in prior_pins.items()}
            pins_note = f"{len(pins)} pins refreshed from this run"
        payload = {"kernels": current,
                   "pins": pins,
                   "floors": prior_floors,
                   "meta": {"source": sources,
                            "threshold": args.threshold}}
        with open(args.baseline, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.baseline} ({len(current)} metrics, "
              f"{pins_note} + {len(prior_floors)} floors preserved)")
        return 0

    if not os.path.exists(args.baseline):
        print(f"skip: no baseline at {args.baseline} — gate disabled "
              f"(create one with --update)")
        return 0
    with open(args.baseline) as f:
        baseline = json.load(f).get("kernels", {})
    baseline.update(prior_pins)   # pinned gate values override measured
    return check(baseline, current, threshold=args.threshold,
                 strict=args.strict, floors=prior_floors)


if __name__ == "__main__":
    sys.exit(main())
