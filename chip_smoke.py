"""Drive SFPrompt's main paths once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: kernels, train, serve
    python chip_smoke.py --four-chips   # only the (data=2, model=2) round

Phases, in order; each prints its own result lines, and any failure exits
non-zero:

  kernels  every Pallas kernel of the main path at real widths (vit-base
           rounds, qwen2.5-14b decode) against its ref/XLA path on the
           same inputs.
  train    vit-base at its published widths through `repro.launch.train`:
           two clear rounds, then one secure-aggregation round from the
           same start, compared with the clear round. Loss, round times,
           metered bytes against `repro.core.comm`, peak device memory.
  serve    `repro.launch.serve` at qwen2.5-14b's published widths (depth
           cut to 3 layers) with the dense and the paged engine, int8
           wire: `--impl auto` at default precision (its decode programs
           checked to run the Pallas kernels) and at 'highest' against an
           `--impl ref` run of the same requests, and the metered bytes
           against `repro.core.comm`.

`--four-chips` runs one vit-base round on a (data=2, model=2) mesh over
four chips and the same round on one device, and compares params and
every metered byte.

Everything is made from `--seed`. The script runs in one process (a chip
belongs to one process at a time) and refuses to run without a TPU. Its
last line of output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import itertools
import json
import math
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
RUNS = ROOT / "runs" / "chip_smoke"

# vit-base federated rounds: 224x224 images, 32 samples per client,
# batch 16, K=2 (a full-width round needs ~3.9 GB of temporaries per
# client; K=8 does not fit one v5e chip)
TRAIN_ARGS = ["--arch", "vit-base", "--dataset", "cifar100-syn",
              "--image-hw", "224", "--clients", "2", "--k", "2",
              "--samples", "64", "--batch-size", "16", "--local-epochs", "2",
              "--prompt-len", "16"]
K = 2
N_LOCAL = 32
TOKENS_PER_IMAGE = (224 // 16) ** 2 + 1

# qwen2.5-14b at its published widths (d_model 5120, 40/8 heads of 128,
# d_ff 13824, vocab 152064), depth cut to head, body and tail of one layer
# each. One tenant: its tail with the 152064-wide output layer is 4.2 GB
# in f32, and a decode step gathers one tail per slot, which with two
# tenants at 4 slots needs 20.3 GB of a v5e's 15.75. Prompts of 100 and
# 300 tokens fill up to 3 of a 512-token window's four 128-token blocks.
SERVE_ARGS = ["--arch", "qwen2.5-14b", "--no-reduced", "--layers", "3",
              "--wire", "int8", "--requests", "12", "--slots", "4",
              "--tenants", "1", "--max-seq", "512",
              "--prompt-choices", "100", "300",
              "--new-token-choices", "8", "16", "--collect-logits"]
PAGED_ARGS = ["--page-size", "16", "--shared-prefix", "24",
              "--prefill-chunk", "8"]


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def report(phase: str, name: str, err: float, tol: float,
           reason: str) -> None:
    print(f"[{phase}] {name}: max err {err:.3e} <= tol {tol:.1e} "
          f"({reason})", flush=True)
    check(err <= tol, f"{name}: max err {err} > {tol}")


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache loads
    included) and counts persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ----------------------------------------------------------------- kernels
MXU_TOL = 2e-2
MXU_WHY = ("f32 dots may take one bf16 MXU pass, 8 significant bits, "
           "against an f32 'highest' reference; outputs are O(1)")


def kernels_phase(seed: int) -> None:
    from repro.configs import get_config
    from repro.core import SplitConfig, SplitModel
    from repro.kernels.el2n.ops import el2n_scores
    from repro.kernels.flash_attention.decode import (decode_attention,
                                                      paged_decode_attention)
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.quant.ops import dequantize_int8, quantize_int8
    from repro.privacy.fixed_point import resolution
    from repro.privacy.secure_agg import SecureAggregator

    root = jax.random.PRNGKey(seed)
    keys = (jax.random.fold_in(root, i) for i in itertools.count())
    normal = lambda shape: jax.random.normal(next(keys), shape)

    # flash forward, vit-base: batch 16, 197 tokens + 16 prompt, 12 x 64
    q, k, v = (normal((16, 213, 12, 64)) for _ in range(3))
    got = flash_attention(q, k, v, causal=False, impl="pallas")
    with jax.default_matmul_precision("highest"):
        want = flash_attention(q, k, v, causal=False, impl="ref")
    report("kernels", "flash_attention vit-base (16,213,12,64)",
           max_err(got, want), MXU_TOL, MXU_WHY)

    # el2n over vit-base logits and a qwen2.5-14b vocabulary
    for vocab in (100, 152064):
        logits = 3.0 * normal((16, vocab))
        labels = jax.random.randint(next(keys), (16,), 0, vocab)
        got = el2n_scores(logits, labels, impl="pallas")
        want = el2n_scores(logits, labels, impl="ref")
        report("kernels", f"el2n (16,{vocab})",
               max(max_err(g, w) for g, w in zip(got, want)), 1e-4,
               "f32 softmax sums over the vocab in another order")

    # int8 wire: vit-base smashed rows, and batch-1 prefill row counts
    for rows in (16 * 213, 12, 20):
        x = 3.0 * normal((rows, 768))
        u = jax.random.uniform(next(keys), x.shape)
        vp, sp = quantize_int8(x, u, impl="pallas")
        vr, sr = quantize_int8(x, u, impl="ref")
        steps = max_err(dequantize_int8(vp, sp, impl="ref"),
                        dequantize_int8(vr, sr, impl="ref")) / float(
                            jnp.min(sr))
        flips = int(jnp.sum(vp != vr))
        report("kernels", f"quantize_int8 ({rows},768), {flips} int8 "
               f"values differ; in quant steps", steps, 1.0,
               "x/scale may differ by an ulp between the two divisions, "
               "moving the stochastic floor by one step")
        report("kernels", f"dequantize_int8 ({rows},768)",
               max_err(dequantize_int8(vr, sr, impl="pallas"),
                       dequantize_int8(vr, sr, impl="ref")), 0.0,
               "one f32 product per value on both paths")

    # decode attention, qwen2.5-14b geometry: 8 slots, 40/8 heads of 128,
    # a 4096-token ring window with ragged per-slot lengths
    B, W, Hq, Hkv, Dh, page = 8, 4096, 40, 8, 128, 16
    q = normal((B, 1, Hq, Dh))
    k, v = normal((B, W, Hkv, Dh)), normal((B, W, Hkv, Dh))
    lens = np.array([1, 100, 513, 1024, 2047, 3000, 4095, 4096])
    pos = np.full((B, W), -1, np.int32)
    for b, n in enumerate(lens):
        pos[b, (np.arange(n) + 97 * b) % W] = np.arange(n)
    qpos, pos = jnp.asarray(lens - 1, jnp.int32), jnp.asarray(pos)
    with jax.default_matmul_precision("highest"):
        want = decode_attention(q, k, v, q_positions=qpos, kv_positions=pos,
                                impl="xla")
    got = decode_attention(q, k, v, q_positions=qpos, kv_positions=pos,
                           impl="pallas")
    report("kernels", "decode_attention (8 slots, W 4096, 40/8x128)",
           max_err(got, want), MXU_TOL, MXU_WHY)

    # the same caches as a shuffled page pool behind block tables
    nb = W // page
    n_pages = 2 + B * nb
    perm = np.random.default_rng(seed).permutation(np.arange(2, n_pages))
    tables = perm.reshape(B, nb)
    flat = jnp.asarray(tables.reshape(-1))

    def to_pool(x, fill):
        rest = x.shape[2:]
        pool = jnp.full((n_pages, page) + rest, fill, x.dtype)
        return pool.at[flat].set(x.reshape((B * nb, page) + rest))

    got = paged_decode_attention(
        q, to_pool(k, 0.0), to_pool(v, 0.0),
        block_tables=jnp.asarray(tables, jnp.int32), q_positions=qpos,
        kv_positions=to_pool(pos, -1), impl="pallas")
    report("kernels", "paged_decode_attention (page 16, same caches)",
           max_err(got, want), MXU_TOL, MXU_WHY)

    # secure mask: the cohort ring sum of vit-base (tail, prompt) uploads,
    # one client dropped, Pallas masks against the ref aggregator's
    model = SplitModel(get_config("vit-base"),
                       SplitConfig(head_cycles=1, tail_cycles=1,
                                   prompt_len=16))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_clients = 4
    tree = jax.tree.map(lambda s: 0.05 * normal((n_clients,) + s.shape),
                        {"tail": shapes["tail"], "prompt": shapes["prompt"]})
    n_values = sum(x.size for x in jax.tree.leaves(tree)) // n_clients
    w = jnp.array([2.0, 1.0, 0.0, 3.0])
    fallback = jax.tree.map(lambda x: jnp.zeros_like(x[0]), tree)
    want, _ = SecureAggregator(impl="ref").aggregate(tree, w, fallback, 1)
    got, _ = SecureAggregator(impl="pallas").aggregate(tree, w, fallback, 1)
    report("kernels", f"secure_mask cohort sum ({n_clients} clients x "
           f"{n_values} values, 1 dropped)",
           max(max_err(g, r) for g, r in zip(jax.tree.leaves(got),
                                             jax.tree.leaves(want))),
           resolution(),
           "masks cancel mod 2^32 and both paths encode the same "
           "integers; one fixed-point step")


# ------------------------------------------------------------------- train
def train_phase(seed: int) -> None:
    from repro.checkpoint import load_checkpoint
    from repro.core.comm import (crosscheck, measured_cost_inputs,
                                 secure_agg_breakdown,
                                 sfprompt_comm_breakdown)
    from repro.launch import train
    from repro.privacy.fixed_point import roundtrip_tol

    base = TRAIN_ARGS + ["--seed", str(seed)]
    clear = train.main(base + ["--rounds", "2", "--ckpt-every", "1",
                               "--out", str(RUNS / "clear")])
    recs = clear["records"]
    for r in recs:
        for key in ("local_loss", "split_loss", "eval_ce"):
            check(math.isfinite(r[key]), f"round {r['round']} {key} = "
                  f"{r[key]}")
        print(f"[train] clear round {r['round']}: local loss "
              f"{r['local_loss']:.4f}, split loss {r['split_loss']:.4f}, "
              f"eval ce {r['eval_ce']:.4f}, round {r['round_s']:.3f} s",
              flush=True)
    print(f"[train] round wall time: first {recs[0]['round_s']:.3f} s "
          f"(includes compiling), steady {recs[1]['round_s']:.3f} s",
          flush=True)

    trainer = clear["trainer"]
    ci = measured_cost_inputs(trainer.model,
                              tokens_per_sample=TOKENS_PER_IMAGE,
                              n_local=N_LOCAL, batch_size=16, K=K)
    for name, e in crosscheck(trainer.meter.per_round(), ci).items():
        print(f"[train] clear {name}: measured {e['measured']:.0f} B/round"
              f" vs analytical {e['analytical']:.0f} ({e['err_pct']:+.3f}%)",
              flush=True)
        check(abs(e["err_pct"]) <= 5.0, f"clear {name} off by > 5%")

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[train] peak_bytes_in_use {peak} of bytes_limit "
          f"{stats.get('bytes_limit')}; memory_stats {stats}", flush=True)
    check(peak is not None, "the device reports no peak_bytes_in_use")

    ckpt = load_checkpoint(str(RUNS / "clear" / "ckpt" / "ckpt_00000001.npz"))
    clear_r1 = ckpt["trainer"]["params"]
    del clear, trainer

    secure = train.main(base + ["--rounds", "1", "--secure-agg",
                                "--out", str(RUNS / "secure")])
    rec = secure["records"][0]
    check(math.isfinite(rec["split_loss"]), "secure split loss")
    print(f"[train] secure round 0: split loss {rec['split_loss']:.4f}, "
          f"round {rec['round_s']:.3f} s (includes compiling)", flush=True)
    got = secure["engine"].params
    def seg_err(segs):
        return max(max_err(x, y) for x, y in zip(
            jax.tree.leaves({s: got[s] for s in segs}),
            jax.tree.leaves({s: clear_r1[s] for s in segs})))

    report("train", "secure vs clear round-1 (tail, prompt)",
           seg_err(("tail", "prompt")), roundtrip_tol(K),
           f"fixed-point encode of {K} clients at 2^-16")
    report("train", "secure vs clear round-1 frozen (head, body)",
           seg_err(("head", "body")), 0.0, "frozen segments never change")

    meter = secure["trainer"].meter.per_round()
    trainable = {"tail": got["tail"], "prompt": got["prompt"]}
    n_trainable = sum(x.size for x in jax.tree.leaves(trainable))
    analytical = dict(sfprompt_comm_breakdown(ci))
    analytical.update(secure_agg_breakdown(
        n_trainable=n_trainable, param_nbytes=4.0 * n_trainable, K=K,
        n_uploads=K))
    for name, e in crosscheck(meter, ci, analytical).items():
        print(f"[train] secure {name}: measured {e['measured']:.0f} B vs "
              f"analytical {e['analytical']:.0f} ({e['err_pct']:+.3f}%)",
              flush=True)
        check(abs(e["err_pct"]) <= 5.0, f"secure {name} off by > 5%")


# ------------------------------------------------------------------- serve
SERVE_TOL = 5e-2
SERVE_WHY = ("one bf16 MXU pass per f32 dot in every layer and the "
             "152064-wide output layer, not only in attention; int8 "
             "stochastic rounding at both boundaries turns a shift into a "
             "whole step, 1/127 of the row max, with probability "
             "shift/step. A 1280-wide cut of the model run in bf16 "
             "throughout moved them by 2.2e-2 of the max")


def served_decode_hlo(eng) -> str:
    """StableHLO of every decode program the engine built, lowered on its
    own state: the attention and wire kernels `impl` picked are named in
    it."""
    S = eng.cfg.n_slots
    ids = jnp.zeros((S,), jnp.int32)
    active = jnp.ones((S,), jnp.float32)
    if eng.cache is None:       # paged: the page pool and block tables
        steps = [(eng._paged_decode, active)] + [
            (f, ids) for f in eng._paged_multi.values()]
        state = (eng.pool, jnp.asarray(eng._tables))
    else:
        steps = [(eng._decode, active)] + [
            (f, ids) for f in eng._multi.values()]
        state = (eng.cache,)
    return "\n".join(
        f.lower(eng.shared, eng.bank.tails, ids, ids, ids, a, *state).as_text()
        for f, a in steps)


def serve_run(argv, *, kernel: str = "") -> dict:
    """`serve.main(argv)`; returns what the checks need and lets go of the
    engine, so the next run has the chip's memory to itself. With
    `kernel`, also checks that every decode program runs that kernel and
    the int8 wire kernel."""
    from repro.core.comm import serve_comm_breakdown
    from repro.launch import serve

    stats = serve.main(argv)
    eng = stats.pop("engine")
    if kernel:
        hlo = served_decode_hlo(eng)
        for name in (kernel, "sfprompt_wire_quantize"):
            check(f'kernel_name = "{name}"' in hlo,
                  f"a served decode program does not run {name}")
        print(f"[serve] decode programs run {kernel} and "
              "sfprompt_wire_quantize", flush=True)
    model = eng.model
    # what one prefix hit does not send: [soft prompt | shared prefix]
    prefix = len(eng.cfg.prefix_tokens or ()) if eng.cache is None else 0
    stats["hit_bytes"] = sum(serve_comm_breakdown(
        model.wire, d_model=model.cfg.d_model,
        soft_prompt_len=model.split.prompt_len,
        requests=[(prefix, 1)]).values()) if prefix else 0.0
    stats["out"] = {f.req.rid: f for f in stats.pop("finished")}
    del eng
    gc.collect()
    return stats


def logit_err(got: dict, want: dict) -> tuple:
    """Largest logit gap relative to the largest |logit|, over every step
    whose input tokens agree (up to and including each request's first
    differing token), and the number of requests whose tokens all agree."""
    worst, same = 0.0, 0
    for rid, w in want.items():
        g = got[rid]
        diff = np.flatnonzero(g.tokens != w.tokens)
        same += diff.size == 0
        n = int(diff[0]) + 1 if diff.size else len(w.tokens)
        worst = max(worst, float(np.max(np.abs(g.logits[:n] - w.logits[:n]))
                                 / np.max(np.abs(w.logits[:n]))))
    return worst, same


def serve_phase(seed: int) -> None:
    from repro.runtime.meter import MB

    for engine, extra, kernel in (
            ("dense", [], "sfprompt_decode_attention"),
            ("paged", PAGED_ARGS, "sfprompt_paged_decode_attention")):
        argv = SERVE_ARGS + extra + ["--seed", str(seed)]
        # 'highest' keeps the f32 dots from rounding to one bf16 pass, in
        # XLA and in the kernels alike, so the two attention paths agree
        # to f32 reassociation and greedy tokens match exactly
        with jax.default_matmul_precision("highest"):
            hi = serve_run(argv + ["--impl", "auto"])
            ref = serve_run(argv + ["--impl", "ref"])
        # the program a user gets: default precision, checked to run the
        # Pallas kernels, against the f32 reference
        auto = serve_run(argv + ["--impl", "auto"], kernel=kernel)
        want = ref["out"]
        check(want.keys() == hi["out"].keys() == auto["out"].keys()
              and len(want) > 0, f"{engine}: served requests differ")
        same = sum(np.array_equal(hi["out"][r].tokens, want[r].tokens)
                   for r in want)
        print(f"[serve] {engine}: {auto['n_finished']} requests, "
              f"{auto['tokens_out']} tokens, {auto['tok_per_s']:.1f} tok/s "
              f"incl. compile; at 'highest' tokens equal to impl=ref in "
              f"{same}/{len(want)} requests", flush=True)
        check(same == len(want), f"{engine}: tokens differ from impl=ref")
        err, same = logit_err(auto["out"], want)
        report("serve", f"{engine} default precision vs impl=ref logits, "
               f"relative to max |logit| ({same}/{len(want)} requests with "
               f"equal tokens)", err, SERVE_TOL, SERVE_WHY)

        measured = [r["wire_bytes"]["total"] for r in (hi, ref, auto)]
        hits = auto.get("prefix_hits", 0)
        expected = (sum(auto["wire_analytical"].values())
                    - hits * auto["hit_bytes"])
        print(f"[serve] {engine} wire: {measured[2]:.0f} B measured vs "
              f"{expected:.0f} B analytical ({expected / MB:.4f} MB; "
              f"{hits} prefix hits of {auto['hit_bytes']:.0f} B each); "
              f"impl=ref and 'highest' measured {measured[1]:.0f}, "
              f"{measured[0]:.0f} B", flush=True)
        check(len(set(measured)) == 1,
              f"{engine}: metered bytes depend on the attention impl")
        check(measured[2] == expected,
              f"{engine}: measured wire bytes differ from the analytical "
              "model")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[serve] peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)


# -------------------------------------------------------------- four chips
def four_chip_phase(seed: int) -> None:
    from repro.launch import train

    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, JAX "
          f"sees {len(jax.devices())}")
    base = TRAIN_ARGS + ["--seed", str(seed), "--rounds", "1"]
    one = train.main(base + ["--out", str(RUNS / "one_device")])
    mesh = train.main(base + ["--mesh-devices", "4", "--mesh-model", "2",
                              "--out", str(RUNS / "mesh")])
    for name, res in (("one device", one), ("(data=2, model=2)", mesh)):
        print(f"[mesh] {name}: round {res['records'][0]['round_s']:.3f} s "
              f"(includes compiling), split loss "
              f"{res['records'][0]['split_loss']:.4f}", flush=True)
    devices = {d for x in jax.tree.leaves(mesh["engine"].params)
               for d in x.sharding.device_set}
    print(f"[mesh] params live on {len(devices)} devices", flush=True)
    check(len(devices) == 4, "the mesh round did not span 4 devices")
    a, b = one["engine"].params, mesh["engine"].params
    err = max(max_err(x, y) for x, y in zip(jax.tree.leaves(a),
                                            jax.tree.leaves(b)))
    scale = max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(a))
    report("mesh", "params, mesh vs one device", err, 1e-5 + 1e-4 * scale,
           "the 'model' all-reduces sum partial products in another "
           "order: f32 reassociation")
    m1, m4 = one["trainer"].meter.totals, mesh["trainer"].meter.totals
    for name in sorted(m1):
        print(f"[mesh] metered {name}: {m4[name]:.0f} B vs one device "
              f"{m1[name]:.0f} B", flush=True)
    check(m1 == m4, "metered bytes differ between mesh and one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=2, model=2) mesh round on 4 "
                         "chips and the same round on one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {dev.platform!r} devices. "
              "This check runs only on a TPU.", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {dev.device_kind} x{len(jax.devices())}, compile "
          f"cache {enable_compile_cache()}", flush=True)
    shutil.rmtree(RUNS, ignore_errors=True)
    clock = CompileClock()
    phases = ([("mesh", four_chip_phase)] if args.four_chips else
              [("kernels", kernels_phase), ("train", train_phase),
               ("serve", serve_phase)])
    for name, phase in phases:
        t0, c0 = time.perf_counter(), clock.seconds
        try:
            phase(args.seed)
        except SmokeFailure as e:
            print(f"[{name}] FAILED: {e}", file=sys.stderr, flush=True)
            return 1
        print(f"[{name}] passed in {time.perf_counter() - t0:.1f} s, "
              f"{clock.seconds - c0:.1f} s of it compiling", flush=True)
    print(f"compile: {clock.seconds:.1f} s in all, {clock.cache_hits} "
          "persistent-cache hits", flush=True)
    shutil.rmtree(RUNS, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
