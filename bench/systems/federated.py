"""Federated SFPrompt rounds: what runs a `rounds` mix, and the plain
reference of the protocol that decides `correct`.

Set-up builds one `FederatedEngine` over the mix's population
(`bench/traffic.py`) with weights and data made from the seed, drives it
through its first `check_rounds` rounds (the first compiles), and hands
that same engine to the window. A round is
`FederatedEngine.run_round()` followed by `block_until_ready` on the new
parameters: cohort sampling, the host gather of the cohort's images, the
jitted round and its metric sync. `round_s` is the window's seconds over
the rounds completed in it.

The reference follows the first rounds from the same weights, data and
cohorts: phase 1 local-loss SGD with momentum through head and tail, EL2N
scoring and the kept subset, phase 2 through head, body and tail, and the
FedAvg weighted by true client size, in plain jax.numpy at 'highest' precision. It is
compared by each round's losses, the norm of each trainable leaf's first
round update and of its change over all the rounds (worst leaf), and the
metered wire bytes of each round.
"""
from __future__ import annotations

import gc
import resource
import time
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, traffic

F32 = jnp.float32


# ------------------------------------------------------------------ program
def keep_count(n_local: int, gamma: float, batch: int) -> int:
    """Samples a client keeps for phase 2: n - floor(gamma n), at least a
    batch, rounded down to whole batches."""
    keep = max(batch, n_local - int(gamma * n_local))
    return keep - keep % batch


def build(r: common.Run, weights, data, rows, sizes):
    """The engine under test, built through the program's public API."""
    from repro.core import ProtocolConfig, SFPromptTrainer
    from repro.core.aggregation import get_aggregator
    from repro.core.split import SplitModel
    from repro.fed import ClientSampler, FederatedEngine, Population
    from repro.runtime import WireSpec

    cfg, mix, prog = r.config, r.traffic, r.config["program"]
    model = SplitModel(
        *common.program_model(cfg, local_epochs=mix["local_epochs"]),
        WireSpec.make(cfg["wire"]))
    pcfg = ProtocolConfig(
        clients_per_round=mix["cohort"], local_epochs=mix["local_epochs"],
        batch_size=mix["batch"], lr_local=prog["lr_local"],
        lr_split=prog["lr_split"], optimizer=prog["optimizer"],
        momentum=prog["momentum"], impl=prog["impl"])
    # the pairwise-mask seed is a constant of the round program: one drawn
    # from the run's seed would compile a new program in every run
    aggregator = (get_aggregator(secure=True, seed=mix["mask_seed"],
                                 frac_bits=mix["frac_bits"])
                  if mix["secure"] else None)
    trainer = SFPromptTrainer(model, pcfg, aggregator)
    population = Population(data, list(rows), sizes)
    engine = FederatedEngine(trainer, population,
                             ClientSampler(mix["clients"], mix["cohort"],
                                           seed=r.seed))
    engine.state = {"params": weights, "round": jnp.zeros((), jnp.int32)}
    return engine


def trainable_np(params) -> Dict[str, Any]:
    return jax.tree.map(np.asarray, {"tail": params["tail"],
                                     "prompt": params["prompt"]})


def first_rounds(r: common.Run):
    """Set-up: the engine from the seed, driven through its first
    `check_rounds` rounds by the window's own call. Returns the engine,
    each round's metrics, the trainable (tail, prompt) before and after
    each round, and the cohorts. Marks each part of set-up on `r`."""
    cfg, mix, ref = r.config, r.traffic, r.ref
    rows, sizes = traffic.population(mix, r.seed)
    w_key, d_key = common.seed_key(r.seed, 0), common.seed_key(r.seed, 1)
    weights = jax.block_until_ready(jax.jit(
        lambda k: ref.to_program(ref.make_weights(cfg, k), cfg))(w_key))
    r.mark("weights")
    patches, labels = jax.jit(partial(ref.make_data, cfg,
                                      n_samples=int(sizes.sum())))(d_key)
    data = {"patches": np.asarray(patches), "labels": np.asarray(labels)}
    del patches, labels
    r.mark("data")
    engine = build(r, weights, data, rows, sizes)
    del weights
    r.plant(engine)
    snaps = [trainable_np(engine.params)]
    first: List[Dict[str, float]] = []
    r.mark("engine")
    for i in range(mix["check_rounds"]):
        _, m = engine.run_round()
        jax.block_until_ready(engine.params)
        first.append(m)
        snaps.append(trainable_np(engine.params))
        r.mark(f"check_round_{i}")
    cohorts = [np.asarray(c) for c in engine.cohort_history]
    return engine, first, snaps, cohorts


def timed_gather(engine) -> List[float]:
    """Time each host gather of a cohort's rows from here on; returns the
    list the seconds go to."""
    seconds: List[float] = []
    real = engine.population.gather

    def gather(cohort):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.gather"):
            out = real(cohort)
        seconds.append(time.perf_counter() - t0)
        return out
    engine.population.gather = gather
    return seconds


def host_counters() -> Dict[str, float]:
    """This process's CPU seconds, page faults and context switches, and
    the machine's CPU ticks by kind (steal: taken by the hypervisor)."""
    use = resource.getrusage(resource.RUSAGE_SELF)
    out = {k: float(getattr(use, "ru_" + k)) for k in (
        "utime", "stime", "minflt", "majflt", "nvcsw", "nivcsw")}
    try:
        with open("/proc/stat") as f:
            ticks = f.readline().split()[1:9]
        out.update(zip(("user", "nice", "system", "idle", "iowait", "irq",
                        "softirq", "steal"), map(float, ticks)))
    except OSError:
        pass
    return out


def run(r: common.Run) -> Dict[str, Any]:
    cfg, mix = r.config, r.traffic
    engine, first, snaps, cohorts = first_rounds(r)

    gathers = timed_gather(engine)
    setup_s = time.time() - r.t_start
    compile_s = r.clock.seconds
    times: List[float] = []
    seconds = r.window_seconds()
    host0 = host_counters()
    with r.window() as win:
        t0 = last = time.perf_counter()
        while last - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.round"):
                engine.run_round()
                jax.block_until_ready(engine.params)
            now = time.perf_counter()
            times.append(now - last)
            last = now
    elapsed = last - t0
    host = {k: v - host0[k] for k, v in host_counters().items()}
    device = common.device_record(r.devices)
    memory = common.memory_note(r.devices)
    compiles_in_window = r.clock.compiles - win.compiles_before
    rounds = len(times)
    window = {"rounds": rounds,
              "cohorts": [c.tolist() for c in
                          engine.cohort_history[mix["check_rounds"]:]],
              "client_sizes": engine.population.sizes.tolist()}

    del engine
    gc.collect()
    keep = keep_count(mix["samples_per_client"], cfg["split"]["prune_gamma"],
                      mix["batch"])
    t_ref, c_ref = time.perf_counter(), r.clock.seconds
    checks, got, timing = compare(r, first, snaps, cohorts, keep)
    t_ref = time.perf_counter() - t_ref
    us = lambda xs: [round(x, 6) for x in xs]
    return {
        "setup_s": setup_s, "compile_s": compile_s,
        "end_to_end": {"round_s": elapsed / rounds},
        "attempted": rounds, "failed": 0,
        "window": window, "device": device, "trace": win.reduced,
        "checks": checks,
        "notes": [f"window: {rounds} rounds in {elapsed:.6f} s, "
                  f"{compiles_in_window} compiles inside it; round s "
                  f"median {np.median(times):.6f} max {max(times):.6f}, "
                  f"host gather s median {np.median(gathers):.6f} max "
                  f"{max(gathers):.6f}",
                  f"host during the window (deltas; machine ticks from "
                  f"/proc/stat): {host}",
                  f"round_s each: {us(times)}",
                  f"gather_s each: {us(gathers)}",
                  memory, f"reference: {t_ref:.3f} s, "
                  f"{r.clock.seconds - c_ref:.3f} s of it compiling; "
                  f"rounds {[round(t, 3) for t in timing]} s; phase-2 "
                  f"loss gap (read, not compared) {got['split_loss']!r}; "
                  f"worst leaf: update {got['update_leaf']}, change "
                  f"{got['change_leaf']}"],
    }


# ---------------------------------------------------------------- reference
def ce_el2n(logits, labels):
    """Per-sample cross-entropy and EL2N (SFPrompt Eq. 2):
    ||softmax(logits) - onehot(label)||_2."""
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=F32)
    ce = -jnp.sum(logp * onehot, axis=-1)
    err = jnp.exp(logp) - onehot
    return ce, jnp.sqrt(jnp.sum(err * err, axis=-1))


def client_round(frozen, trainable, patches, labels, rows, *, ref, cfg,
                 mix, keep, dtype=F32, half_batch=False):
    """One client's phases 1 and 2 on its `rows` of the dataset. Returns
    (trainable, mean local loss, mean split loss)."""
    prog = cfg["program"]
    bs, U = mix["batch"], mix["local_epochs"]
    nb = rows.shape[0] // bs
    # the trainable state and its momentum are kept in `dtype` too
    trainable = jax.tree.map(lambda a: a.astype(dtype), trainable)
    patches, labels = patches[rows], labels[rows]

    def local_logits(t, x):
        h = ref.head_forward(frozen, t["prompt"], x, cfg, dtype)
        return ref.tail_forward(t, h, cfg, dtype)

    def split_logits(t, x):
        h = ref.head_forward(frozen, t["prompt"], x, cfg, dtype)
        h = ref.body_forward(frozen, h, cfg, dtype)
        return ref.tail_forward(t, h, cfg, dtype)

    def loss(f, t, x, y):
        if half_batch:
            x, y = x[: bs // 2], y[: bs // 2]
        return ce_el2n(f(t, x), y)[0].mean()

    def sgd(f, t, xs, ys, steps, lr):
        """`steps` SGD-with-momentum updates over batches cycled in order;
        each step's loss is taken before its update."""
        def step(carry, i):
            t, m = carry
            j = i % xs.shape[0]
            val, g = jax.value_and_grad(partial(loss, f))(t, xs[j], ys[j])
            m = jax.tree.map(lambda a, b: (prog["momentum"] * a + b)
                             .astype(dtype), m, g)
            t = jax.tree.map(lambda p, a: (p - lr * a).astype(dtype), t, m)
            return (t, m), val
        m0 = jax.tree.map(jnp.zeros_like, t)
        (t, _), vals = jax.lax.scan(step, (t, m0), jnp.arange(steps))
        return t, vals.mean()

    xs = patches[: nb * bs].reshape((nb, bs) + patches.shape[1:])
    ys = labels[: nb * bs].reshape(nb, bs)
    local = jnp.float32(0.0)
    if U > 0:
        trainable, local = sgd(local_logits, trainable, xs, ys, U * nb,
                               prog["lr_local"])
    scores = jax.lax.map(lambda b: ce_el2n(local_logits(trainable, b[0]),
                                           b[1])[1], (xs, ys)).reshape(-1)
    order = jnp.argsort(-scores)[:keep]
    kx = patches[order].reshape((keep // bs, bs) + patches.shape[1:])
    ky = labels[order].reshape(keep // bs, bs)
    trainable, split = sgd(split_logits, trainable, kx, ky, keep // bs,
                           prog["lr_split"])
    return trainable, local, split


def weighted_mean(xs, sizes, *, bits=None):
    """FedAvg of the clients' trees `xs`, each weighted by its true size.
    With `bits`, what secure aggregation delivers: each client's share
    x * size / total rounded to `bits` fractional bits (ties to even),
    summed exactly, and read back; the pairwise masks cancel in the ring
    and leave no trace."""
    w = jnp.asarray(sizes, F32)
    share = w / w.sum()
    if bits is None:
        total = sum(x.astype(F32) * s for x, s in zip(xs, share))
    else:
        scale = 2.0 ** bits
        total = sum(jnp.round(x.astype(F32) * s * scale)
                    for x, s in zip(xs, share)) / scale
    return total.astype(xs[0].dtype)


def reference_rounds(r: common.Run, cohorts, keep, *, dtype=F32,
                     half_batch=False, timing=None):
    """The reference's first rounds from the seed: per round the global
    trainable (in the program's layout), its mean losses, and the wire
    bytes it sends. `timing` collects each round's seconds."""
    cfg, mix, ref = r.config, r.traffic, r.ref
    timing = [] if timing is None else timing
    rows, sizes = traffic.population(mix, r.seed)
    bits = mix["frac_bits"] if mix["secure"] else None
    with jax.default_matmul_precision("highest"):
        w = common.jit_once(ref.make_weights, cfg=cfg)(
            key=common.seed_key(r.seed, 0))
        patches, labels = common.jit_once(
            ref.make_data, cfg=cfg, n_samples=int(sizes.sum()))(
                key=common.seed_key(r.seed, 1))
        frozen, glob = ref.split(w, cfg)
        one = common.jit_once(client_round, ref=ref, cfg=cfg, mix=mix,
                              keep=keep, dtype=dtype, half_batch=half_batch)
        out = [jax.tree.map(np.asarray, ref.program_trainable(glob))]
        losses = []
        for cohort in cohorts:
            t0 = time.perf_counter()
            results = [one(frozen, glob, patches, labels, jnp.asarray(rows[c]))
                       for c in cohort]
            jax.block_until_ready(results)
            timing.append(time.perf_counter() - t0)
            glob = jax.tree.map(
                lambda *xs: weighted_mean(xs, sizes[cohort], bits=bits),
                *[t for t, _, _ in results])
            losses.append({
                "local_loss": float(np.mean([float(l) for _, l, _ in results])),
                "split_loss": float(np.mean([float(s) for _, _, s in results]))})
            out.append(jax.tree.map(np.asarray, ref.program_trainable(glob)))
    s = ref.sizes(cfg)
    K = mix["cohort"]
    smashed = K * (keep // mix["batch"]) * 2 * 4 * mix["batch"] * s["T"] * s["D"]
    n_trainable = sum(x.size for x in jax.tree.leaves(out[0]))
    wire = {"head_body": smashed, "body_tail": smashed}
    if not mix["secure"]:
        wire["params"] = 2 * K * 4 * n_trainable
    return out, losses, wire


def leaf_norms(a, b) -> Dict[str, float]:
    """{leaf path: ||a - b||} over the trainable tree."""
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree.leaves(b)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64) - np.asarray(y, np.float64)))
        for (p, x), y in zip(flat_a, flat_b)}


def norm_gap(prog: Dict[str, float], want: Dict[str, float],
             skip) -> Tuple[float, str]:
    """Worst leaf's |prog norm - reference norm|, over the larger of that
    leaf's reference norm and the median leaf's, and that leaf's path."""
    keys = [k for k in want if k not in skip]
    med = float(np.median([want[k] for k in keys]))
    return max((abs(prog[k] - want[k]) / max(want[k], med), k)
               for k in keys)


def readings(snaps, first, ref_snaps, ref_losses, ref_wire):
    """The numbers `correct` compares, program against reference."""
    upd_ref = leaf_norms(ref_snaps[1], ref_snaps[0])
    med = float(np.median(list(upd_ref.values())))
    # leaves the reference does not move (gradient nought to rounding)
    # move by round-off alone and are left out, by this rule and not by name
    skip = {k for k, v in upd_ref.items() if v < 1e-3 * med}
    last = len(ref_snaps) - 1
    gap = lambda k: max(abs(first[i][k] - ref_losses[i][k])
                        / abs(ref_losses[i][k])
                        for i in range(len(ref_losses)))
    upd, upd_leaf = norm_gap(leaf_norms(snaps[1], snaps[0]), upd_ref, skip)
    chg, chg_leaf = norm_gap(leaf_norms(snaps[last], snaps[0]),
                             leaf_norms(ref_snaps[last], ref_snaps[0]), skip)
    wire = max(abs(first[i][f"wire/{k}_bytes"] - v)
               for i in range(len(ref_losses)) for k, v in ref_wire.items())
    # the phase-2 loss is read but not compared: it is the mean over the
    # samples EL2N kept, and a score within rounding of the cut swaps a
    # sample in or out (PERF.md, section 4)
    return {"loss": gap("local_loss"), "split_loss": gap("split_loss"),
            "update": upd, "change": chg, "wire_bytes": wire,
            "skipped_leaves": len(skip), "update_leaf": upd_leaf,
            "change_leaf": chg_leaf}


def compare(r: common.Run, first, snaps, cohorts, keep):
    timing: List[float] = []
    ref_snaps, ref_losses, ref_wire = reference_rounds(r, cohorts, keep,
                                                       timing=timing)
    got = readings(snaps, first, ref_snaps, ref_losses, ref_wire)
    return [{"name": k, "value": got[k], "limit": r.limits[k]["limit"]}
            for k in ("loss", "update", "change", "wire_bytes")], got, timing
