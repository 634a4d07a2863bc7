"""Compile each cell's programs for a described TPU v5e, without the chip,
and print what the compiler says they need.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

For each cell: the federated round at the cell's cohort (clear or secure
aggregation); for a serving cell, the engine's prefill at each prompt
length of the mix, its paged decode at each bucket (one token, and each
power of two up to `decode_block`, logits kept as the check requests
keep them), and the reference's program that `correct` runs. Every
argument is a shape on one described chip; nothing runs. Kernels are
asked for by name (`impl="pallas"`), since code that asks which backend
it is on sees the CPU here. Prints one JSON line per program with
`memory_analysis()` in bytes.
"""
from __future__ import annotations

import json
import os
import sys
from functools import partial
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import common  # noqa: E402


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}


def on(chip, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=chip), tree)


def federated(spec, chip):
    from repro.core import ProtocolConfig, SFPromptTrainer
    from repro.core.aggregation import get_aggregator
    from repro.core.split import SplitModel
    from repro.runtime import WireSpec

    cfg, mix = spec["config"], spec["traffic"]
    ref = common.load_module(spec["reference"])
    prog = cfg["program"]
    model = SplitModel(
        *common.program_model(cfg, local_epochs=mix["local_epochs"]),
        WireSpec.make(cfg["wire"], impl="pallas"))
    trainer = SFPromptTrainer(model, ProtocolConfig(
        clients_per_round=mix["cohort"], local_epochs=mix["local_epochs"],
        batch_size=mix["batch"], lr_local=prog["lr_local"],
        lr_split=prog["lr_split"], momentum=prog["momentum"],
        impl=prog["impl"]),
        get_aggregator(secure=True, impl="pallas", seed=mix["mask_seed"],
                       frac_bits=mix["frac_bits"]) if mix["secure"]
        else None)
    params = jax.eval_shape(lambda k: ref.to_program(
        ref.make_weights(cfg, k), cfg), jax.random.PRNGKey(0))
    s = ref.sizes(cfg)
    K, n = mix["cohort"], mix["samples_per_client"]
    f32 = jnp.float32
    state = {"params": params, "round": jax.ShapeDtypeStruct((), jnp.int32)}
    data = {"patches": jax.ShapeDtypeStruct((K, n, s["N"], s["P"]), f32),
            "labels": jax.ShapeDtypeStruct((K, n), jnp.int32)}
    part = {"transmit": jax.ShapeDtypeStruct((K,), f32),
            "aggregate": jax.ShapeDtypeStruct((K,), f32)}
    compiled = jax.jit(trainer._round).lower(
        on(chip, state), on(chip, data), on(chip, part), None).compile()
    yield f"round K={K} {'secure' if mix['secure'] else 'clear'}", compiled


def serving(spec, chip):
    from bench.systems import serving as sv
    from repro.core.split import SplitModel
    from repro.runtime import WireSpec
    from repro.serve.steps import (make_paged_decode_step,
                                   make_paged_multi_decode_step,
                                   make_tenant_prefill_step)

    cfg, mix = spec["config"], spec["traffic"]
    ref, srv, sp = common.load_module(spec["reference"]), cfg["serve"], \
        cfg["split"]
    model = SplitModel(*common.program_model(cfg), WireSpec.make(cfg["wire"]))
    w = jax.eval_shape(lambda k: ref.make_weights(cfg, k),
                       jax.random.PRNGKey(0))
    prog = ref.to_program(w, cfg)
    one = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), t)
    tail, prompt = one(prog["tails"]), one(prog["prompts"])
    S, page = srv["n_slots"], srv["page_size"]
    nb = -(-srv["max_seq"] // page)
    pool = jax.eval_shape(lambda: model.init_paged_cache(S * nb + 2, page))
    blank = jax.eval_shape(lambda: model.blank_slot_cache(nb * page))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    prefill = jax.jit(make_tenant_prefill_step(model, impl="pallas"))
    for p in sorted(mix["prompt_lengths"]):
        yield f"prefill {p} tokens", prefill.lower(
            on(chip, prog["shared"]), on(chip, tail),
            on(chip, prompt), on(chip, {"tokens": i32(1, p)}),
            on(chip, blank)).compile()
    n = 1
    while n <= srv["decode_block"]:
        step = (make_paged_decode_step(model, impl="pallas") if n == 1 else
                make_paged_multi_decode_step(model, n, impl="pallas"))
        last = (jax.ShapeDtypeStruct((S,), jnp.float32) if n == 1
                else i32(S))
        yield f"decode {n} x {S} slots", jax.jit(
            step, donate_argnums=(6,)).lower(
            on(chip, prog["shared"]), on(chip, prog["tails"]),
            on(chip, i32(S)), on(chip, i32(S)), on(chip, i32(S)),
            on(chip, last),
            on(chip, pool), on(chip, i32(S, nb))).compile()
        n *= 2
    N = max(mix["output_lengths"])
    gaps = jax.jit(partial(sv._reference_gaps, ref=ref, cfg=cfg,
                           prec=sv.precision(cfg["matmul_precision"]),
                           with_logits=True, control=False))
    yield "reference", gaps.lower(
        on(chip, w), on(chip, i32(srv["max_seq"] - sp["prompt_len"])),
        on(chip, i32()), on(chip, i32()), on(chip, i32(N)),
        on(chip, jax.ShapeDtypeStruct((N, cfg["vocab_size"]),
                                      jnp.float32))).compile()


def main(argv) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = common.benchmark()
    cells = argv or [w["name"] for w in bench["workloads"]]
    for cell in cells:
        spec = common.resolve(cell, bench)
        programs = (serving if spec["config"]["system"] == "serving"
                    else federated)
        for what, compiled in programs(spec, chip):
            print(json.dumps({"cell": cell, "program": what,
                              **memory(compiled)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
