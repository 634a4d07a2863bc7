"""Readings that the limits of `correct` are set from. Not run by the
benchmark's own runs.

    python3 bench/calibrate.py --workload <cell> [--seconds S] [--faults]
        seed [seed ...]

For each seed, in one process: the program's readings against the plain
reference, as a run of the cell takes them, and the same readings of

- the control: the reference in the program's place, computed in the
  precision below the configuration's (bfloat16 for float32 at default
  precision);
- each fault the cell can have. Federated rounds: planted in the
  reference put in the program's place, half of each batch left out with
  the mean taken over the rest (a state returned unchanged reads 1 by
  construction and needs no run). A system module with a `calibration`
  generator (split serving) reads its program through a window of
  `--seconds` and, with `--faults`, plants each of its faults in the
  program and reads it through the check requests.

One JSON line per reading on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import common  # noqa: E402


def readings(r, fed):
    import jax.numpy as jnp
    engine, first, snaps, cohorts = fed.first_rounds(r)
    del engine
    gc.collect()
    cfg, mix = r.config, r.traffic
    keep = fed.keep_count(mix["samples_per_client"],
                          cfg["split"]["prune_gamma"], mix["batch"])
    timing = []
    ref_snaps, ref_losses, ref_wire = fed.reference_rounds(r, cohorts, keep,
                                                           timing=timing)
    print(f"reference rounds {timing} s", file=sys.stderr, flush=True)
    yield "program", fed.readings(snaps, first, ref_snaps, ref_losses,
                                  ref_wire)
    wire = {f"wire/{k}_bytes": v for k, v in ref_wire.items()}
    for who, kw in (("control", {"dtype": jnp.bfloat16}),
                    ("half_batch", {"half_batch": True})):
        timing = []
        s, losses, _ = fed.reference_rounds(r, cohorts, keep, timing=timing,
                                            **kw)
        print(f"{who}: reference rounds {timing} s", file=sys.stderr,
              flush=True)
        yield who, fed.readings(s, [dict(l, **wire) for l in losses],
                                ref_snaps, ref_losses, ref_wire)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0,
                    help="window of a system with `calibration`")
    ap.add_argument("--faults", action="store_true",
                    help="also plant and read that system's faults")
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    spec = common.resolve(args.workload)
    common.prepare_environment()
    try:
        devices = common.require_chips(spec["cell"]["chips"])
    except common.NoDevice as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 2
    clock = common.CompileClock()
    system = common.load_module(spec["system"])
    for seed in args.seeds:
        t0 = time.time()
        r = common.Run(config=spec["config"], traffic=spec["traffic"],
                       ref=common.load_module(spec["reference"]),
                       limits=None, seed=seed, seconds=args.seconds,
                       devices=devices, clock=clock, t_start=t0)
        gen = (system.calibration(r, faults=args.faults)
               if hasattr(system, "calibration") else readings(r, system))
        for who, got in gen:
            print(json.dumps({"cell": args.workload, "seed": seed,
                              "who": who, **got,
                              "wall_s": time.time() - t0,
                              "device": devices[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
