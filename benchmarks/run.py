"""Benchmark driver: one function per paper table/figure + the roofline.
Prints ``name,us_per_call,derived`` CSV lines and writes JSON artifacts to
benchmarks/results/. Set REPRO_BENCH_FAST=1 for a quick pass.

Suites import LAZILY and fail INDEPENDENTLY: a suite whose module does not
even import (a broken dependency, a renamed symbol) is recorded as that
suite's failure and the driver moves on — the other suites still run and
the exit code still goes non-zero. `--only <suite>` (repeatable) runs a
subset, which is how CI shards the bench job; `--list` shows the names.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

# name -> (module, description)
SUITES = {
    "comm_cost": ("benchmarks.comm_cost", "Table 2 / Fig 2"),
    "compute_burden": ("benchmarks.compute_burden", "Table 2"),
    "latency_model": ("benchmarks.latency_model", "Table 1"),
    "roofline": ("benchmarks.roofline", "deliverable g"),
    "perf_compare": ("benchmarks.perf_compare", "baseline vs optimized"),
    "kernel_microbench": ("benchmarks.kernel_microbench", "kernel wall times"),
    "serve": ("benchmarks.serve_throughput",
              "serving engine tok/s + latency"),
    "decode": ("benchmarks.decode_throughput",
               "decode fast path: scan stepping + decode attention"),
    "serve_paged": ("benchmarks.serve_paged",
                    "paged KV: slots at fixed HBM + prefix reuse"),
    "secure": ("benchmarks.secure_agg",
               "privacy engine: secure-agg overhead + mask kernel"),
    "population": ("benchmarks.population_scale",
                   "mega-cohort rounds: clients/sec + bytes/round"),
    "mesh_tp": ("benchmarks.mesh_tp",
                "tensor-parallel body: per-device HBM ratio + round time"),
    "async": ("benchmarks.async_rounds",
              "buffered-async vs sync barrier round throughput"),
    "accuracy": ("benchmarks.accuracy", "Table 3 / Fig 4"),
    "prompt_length": ("benchmarks.prompt_length", "Fig 5"),
    "ablation_local_loss": ("benchmarks.ablation_local_loss", "Fig 6"),
    "ablation_pruning": ("benchmarks.ablation_pruning", "Fig 7"),
}


def run_suite(name: str) -> tuple:
    """(ok, seconds). Import errors count as THIS suite's failure."""
    module_name, desc = SUITES[name]
    t0 = time.time()
    print(f"# === {name} ({desc}) ===", flush=True)
    try:
        module = importlib.import_module(module_name)
        module.run()
        return True, time.time() - t0
    except Exception:
        traceback.print_exc()
        return False, time.time() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", action="append", default=None,
                    metavar="SUITE", choices=list(SUITES),
                    help="run only this suite (repeatable)")
    ap.add_argument("--list", action="store_true",
                    help="print suite names and exit")
    args = ap.parse_args(argv)
    if args.list:
        for name, (_, desc) in SUITES.items():
            print(f"{name:>22}  {desc}")
        return 0

    names = args.only or list(SUITES)
    print("name,us_per_call,derived")
    results = {}
    for name in names:
        results[name] = run_suite(name)

    failures = [n for n, (ok, _) in results.items() if not ok]
    print("# --- summary ---")
    for name, (ok, secs) in results.items():
        print(f"# {name:>22}: {'ok' if ok else 'FAILED'} ({secs:.1f}s)")
    if failures:
        print(f"# {len(failures)}/{len(results)} suites FAILED: {failures}")
        return 1
    print(f"# all {len(results)} benchmark suites completed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
