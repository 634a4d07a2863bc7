"""Continuous-batching split-serving launcher.

Runs the `serve.ServeEngine` — slot-based shared KV cache, interleaved
prefill/decode so requests join in-flight batches, per-tenant
(tail, prompt) from a `TenantBank` — against the deterministic synthetic
workload (Poisson arrivals, mixed prompt/output lengths, pure function of
--seed). Reports tokens/s, p50/p99 latency, slot occupancy, and the
measured smashed-tensor wire traffic next to the analytical per-token
model.

Decode fast path knobs: `--decode-block N` steps N tokens per scanned
dispatch (1 = per-token), `--impl` picks the decode-attention kernel
(Pallas on TPU, grouped XLA elsewhere, `ref` = the jnp oracle), and
`--no-donate` disables KV-cache buffer donation into the jitted steps.
`--wire {fp32,bf16,int8}` sets the smashed-tensor codec on both
boundaries. docs/ROUND_LIFECYCLE.md traces one token through the stack.

Paged engine knobs: `--page-size N` (N > 0) swaps in the
`PagedServeEngine` — page-pool KV with per-slot block tables — with
`--n-pages` sizing the pool (default: one full window per slot),
`--shared-prefix K` prepending K deterministic common-prefix tokens to
every request with copy-on-write page sharing across same-tenant
requests, and `--prefill-chunk C` streaming prompts in C-token pieces.
Paging never changes the wire protocol; a prefix HIT honestly meters
fewer prefill bytes, so measured <= analytical when sharing kicks in.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \\
      --requests 16 --slots 8 --tenants 4 --wire int8
  PYTHONPATH=src python -m repro.launch.serve --page-size 16 \\
      --shared-prefix 24 --prefill-chunk 8
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.checkpoint import load_checkpoint
from repro.configs import get_config
from repro.core import SplitConfig, SplitModel
from repro.core.comm import serve_comm_breakdown
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ModelConfig
from repro.obs import MetricsRegistry, export_all, make_tracer
from repro.obs.trace import LEVELS
from repro.runtime import WireSpec
from repro.runtime.meter import MB
from repro.serve import (PagedServeConfig, PagedServeEngine, ServeConfig,
                         ServeEngine, TenantBank, WorkloadConfig,
                         synthetic_requests)


def personalized_bank(model: SplitModel, params, n_tenants: int,
                      *, jitter: float = 0.05) -> TenantBank:
    """A demo TenantBank: tenant 0 serves the aggregated global
    (tail, prompt); every other tenant gets a deterministically perturbed
    copy, standing in for the per-client tails a federation run stores in
    the Population (see examples/serve_tenants.py for the real flow)."""
    tails, prompts = [], []
    for t in range(n_tenants):
        if t == 0 or jitter == 0.0:
            tails.append(params["tail"])
            prompts.append(params["prompt"])
            continue
        key = jax.random.fold_in(jax.random.PRNGKey(101), t)
        leaves, treedef = jax.tree.flatten(params["tail"])
        ks = jax.random.split(key, len(leaves) + 1)
        tails.append(jax.tree.unflatten(treedef, [
            x + jitter * jax.random.normal(k, x.shape, x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x
            for x, k in zip(leaves, ks[:-1])]))
        prompts.append(params["prompt"] + jitter * jax.random.normal(
            ks[-1], params["prompt"].shape, params["prompt"].dtype))
    return TenantBank.from_lists(tails, prompts)


def served_config(arch: str, *, reduced: bool = True,
                  layers: int | None = None) -> ModelConfig:
    """The reduced same-family config, or the published widths with only
    the depth cut to `layers` (None keeps the published depth)."""
    cfg = get_config(arch)
    if reduced:
        # at least 3 layer-pattern cycles so head/body/tail are all non-empty
        return cfg.reduced(n_layers=layers or 3 * len(cfg.layer_pattern))
    if layers:
        return dataclasses.replace(cfg, name=f"{cfg.name}-{layers}L",
                                   n_layers=layers)
    return cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="CPU-sized same-family config (on by default; "
                         "--no-reduced serves the published widths)")
    ap.add_argument("--layers", type=int, default=None,
                    help="layer count: with --reduced, the reduced config's "
                         "(default three layer-pattern cycles); with "
                         "--no-reduced, cuts only the depth of the published "
                         "config (default: its full depth)")
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic workload length")
    ap.add_argument("--slots", type=int, default=8,
                    help="concurrent sequences in the shared KV cache")
    ap.add_argument("--tenants", type=int, default=4,
                    help="distinct (tail, prompt) pairs in the TenantBank")
    ap.add_argument("--max-seq", type=int, default=128,
                    help="KV-cache capacity per slot (prompt + new tokens)")
    ap.add_argument("--mean-interarrival", type=float, default=1.0,
                    help="Poisson arrival gap in engine steps")
    ap.add_argument("--prompt-choices", type=int, nargs="+",
                    default=[8, 16, 32],
                    help="prompt lengths the workload draws from")
    ap.add_argument("--new-token-choices", type=int, nargs="+",
                    default=[4, 8, 16],
                    help="output lengths the workload draws from")
    ap.add_argument("--decode-block", type=int, default=8,
                    help="decode fast path: tokens per scanned dispatch "
                         "(1 = per-token stepping)")
    ap.add_argument("--impl", default="auto",
                    choices=("auto", "ref", "pallas", "interpret"),
                    help="attention impl: auto = the decode-attention "
                         "kernel on TPU, the grouped XLA path elsewhere; "
                         "ref = the jnp oracle")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable KV-cache donation into the jitted steps")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page; > 0 serves with the paged "
                         "engine, 0 (default) keeps the dense slot cache")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="page-pool size incl. the 2 reserved pages "
                         "(default: one full window per slot)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many deterministic common-prefix "
                         "tokens to every request, shared copy-on-write "
                         "across same-tenant requests (paged engine only)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="stream prompts in pieces of this many tokens "
                         "(paged engine only; default: monolithic)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="serve tensor-parallel over a (data, model) host "
                         "mesh with a 'model' axis of this size (frozen "
                         "body sharded, KV kv-heads sharded; must divide "
                         "the visible device count; 1 = single-device)")
    ap.add_argument("--collect-logits", action="store_true",
                    help="keep every generated token's logits in the "
                         "returned per-request results (to compare runs)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--params", default=None,
                    help="checkpoint to serve (e.g. a training run's "
                         "final.npz); default: fresh random init")
    ap.add_argument("--wire", default="fp32", choices=("fp32", "bf16", "int8"),
                    help="codec for the smashed tensors on both boundaries")
    ap.add_argument("--trace-out", default=None,
                    help="flight-recorder export basename: writes "
                         "<base>.jsonl, <base>.trace.json (Chrome/Perfetto) "
                         "and <base>.prom; implies --trace-level round")
    ap.add_argument("--trace-level", default="off", choices=list(LEVELS),
                    help="flight-recorder detail: off = zero-overhead noop, "
                         "round = admission/prefill/retire spans + meter "
                         "bytes, step = decode steps and page churn too")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="print a metrics-registry snapshot every N engine "
                         "steps (0 = only at the end when tracing is on)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = served_config(args.arch, reduced=args.reduced, layers=args.layers)
    split = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=4)
    wire = WireSpec.make(args.wire)
    model = SplitModel(cfg, split, wire)
    params = model.init(jax.random.PRNGKey(0))
    if args.params:
        loaded = load_checkpoint(args.params)
        params = jax.tree.map(jnp.asarray, loaded)

    trace_level = args.trace_level
    if args.trace_out and trace_level == "off":
        trace_level = "round"
    tracer = make_tracer(trace_level)

    bank = personalized_bank(model, params, args.tenants)
    # the bank holds the tails: drop the init's own tail so one copy stays
    shared = {"head": params["head"], "body": params["body"]}
    del params
    mesh = None
    if args.mesh_model > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.mesh_model)
    if args.page_size > 0:
        # deterministic synthetic shared prefix (a pure function of its
        # length), standing in for a common system prompt
        prefix = tuple(int(1 + (i * 13) % (cfg.vocab_size - 1))
                       for i in range(args.shared_prefix))
        engine = PagedServeEngine(
            model, shared, bank,
            PagedServeConfig(n_slots=args.slots, max_seq=args.max_seq,
                             decode_block=args.decode_block,
                             donate=not args.no_donate, impl=args.impl,
                             page_size=args.page_size,
                             n_pages=args.n_pages,
                             shared_prefix=prefix or None,
                             prefill_chunk=args.prefill_chunk),
            collect_logits=args.collect_logits, mesh=mesh, tracer=tracer)
    else:
        if args.shared_prefix or args.prefill_chunk:
            raise SystemExit("--shared-prefix/--prefill-chunk need the "
                             "paged engine: pass --page-size N")
        engine = ServeEngine(model, shared, bank,
                             ServeConfig(n_slots=args.slots,
                                         max_seq=args.max_seq,
                                         decode_block=args.decode_block,
                                         donate=not args.no_donate,
                                         impl=args.impl),
                             collect_logits=args.collect_logits,
                             mesh=mesh, tracer=tracer)
    reqs = synthetic_requests(WorkloadConfig(
        n_requests=args.requests,
        mean_interarrival=args.mean_interarrival,
        prompt_choices=tuple(args.prompt_choices),
        new_token_choices=tuple(args.new_token_choices),
        n_tenants=args.tenants, vocab_size=cfg.vocab_size,
        seed=args.seed))

    registry = MetricsRegistry()
    registry.bind_engine(engine)
    if args.page_size > 0:
        registry.bind_pool(engine.pool_alloc)

    on_step = None
    if args.metrics_every:
        import json as _json

        def on_step(step_idx, _every=args.metrics_every):
            if step_idx % _every == 0:
                print(_json.dumps({"step": step_idx,
                                   "metrics": registry.snapshot()},
                                  sort_keys=True, default=str), flush=True)
    stats = engine.run(reqs, on_step=on_step)

    print(f"{cfg.name}: {stats['n_finished']} requests over "
          f"{args.tenants} tenants | {stats['tokens_out']} tokens in "
          f"{stats['wall_s']:.2f}s = {stats['tok_per_s']:.1f} tok/s "
          f"(incl. compile)")
    print(f"latency p50 {stats['p50_latency_s'] * 1e3:.0f} ms | "
          f"p99 {stats['p99_latency_s'] * 1e3:.0f} ms | "
          f"occupancy {stats['occupancy']:.2f} | "
          f"{stats['prefills']} prefills / {stats['decode_steps']} "
          f"decode steps | rejected {stats['rejected']}")
    measured = stats["wire_bytes"]
    # compare against what was actually SERVED — admission control may
    # have rejected part of the trace, and rejected requests never cross
    # the wire. A shared prefix counts toward every served request's
    # prompt here; prefix HITS skip re-transmitting those activations, so
    # the measured total dips below analytical as the hit ratio climbs.
    prefix_n = args.shared_prefix if args.page_size > 0 else 0
    analytical = serve_comm_breakdown(
        wire, d_model=cfg.d_model, soft_prompt_len=split.prompt_len,
        requests=[(len(f.req.tokens) + prefix_n, f.req.max_new)
                  for f in stats["finished"]])
    print(f"wire [{wire.describe()}]: {measured['total'] / MB:.3f} MB "
          f"measured ({measured['head_body'] / MB:.3f} head_body + "
          f"{measured['body_tail'] / MB:.3f} body_tail) vs "
          f"{sum(analytical.values()) / MB:.3f} MB analytical")
    if args.page_size > 0:
        print(f"pages: {stats['n_pages']} x {stats['page_size']} tok | "
              f"peak {stats['peak_pages']} | "
              f"in use {stats['pages_in_use']} | "
              f"COW copies {stats['page_copies']} | "
              f"prefix hits {stats['prefix_hits']}/"
              f"{stats['prefix_hits'] + stats['prefix_misses']} "
              f"(ratio {stats['prefix_hit_ratio']:.2f}) | "
              f"prefill chunks {stats['prefill_chunks']}")
    if tracer.enabled and args.trace_out:
        paths = export_all(tracer, args.trace_out, meter=engine.meter,
                           registry=registry)
        for fmt, p in sorted(paths.items()):
            print(f"trace [{fmt}]: {p}", flush=True)
    elif tracer.enabled:
        import json as _json
        print(_json.dumps({"metrics": registry.snapshot()}, sort_keys=True,
                          default=str), flush=True)
    stats["wire_analytical"] = analytical
    stats["engine"] = engine
    return stats


if __name__ == "__main__":
    main()
