"""Split serving: what runs a `closed` mix, and the comparison with the
plain reference that decides `correct`.

Set-up makes the weights on the device from the seed in one jitted call
and hands the same buffers to a `PagedServeEngine`: the shared head and
body, and a `TenantBank` whose tails and soft prompts are the seed's. It
then serves the check requests, one per slot and decoded together, with
the engine's logits collected. That compiles, or loads from the cache,
every prefill length and decode bucket the mix uses. The window drives
the same engine and the same compiled programs, the host no longer
reading their logits: a closed loop in which, at the return of each
`engine.step()`, every caller whose request finished sends its next.
`tokens_per_s` is the engine's `tokens_out` over the steps that returned
inside the window, over the window's seconds. Requests in flight when
the window closes drain after it; one that does not finish within the
mix's `drain_seconds` has failed.

A traced run builds the engine with a live flight recorder at level
"step". Its `serve.admit`, `serve.decode` and `serve.retire` records give
the window's decode iterations: the slots active in each, their tenants
and how many positions each slot's cache holds, from which the metric
readers count their own bytes and FLOPs.

`correct` is decided once the window has closed and the engine is freed,
by the plain reference (`bench/configs/<config>.py`) over the same weight
buffers, one request at a time, teacher-forced on the tokens the engine
served. The reference computes at the matmul precision the configuration
states (`matmul_precision`; for qwen2.5-14b the default: the float32 dots
run as one bfloat16 MXU pass, as the program's do), because against
exact float32 that arithmetic alone lies about as far off as the
bfloat16 control (PERF.md, section 4). The numbers are averages, which
the control moves by more than three times what a sound run reads; the
largest gaps swing from seed to seed and are printed, not compared:

- logits: the root mean square of the check requests' logits against
  the reference's, each over its request's largest |reference logit|;
- margin: for the check requests and a sample of the window's finished
  requests drawn from the seed, with the longest in it, the mean gap by
  which a served token's reference logit lies below the reference's best
  at that position, over the largest |reference logit|;
- wire_bytes: the engine's metered wire bytes against the count from
  shapes (fp32 rows: every prefill position and every decoded token
  crosses both boundaries once), exact;
- tokens_counted: the engine's token counter over the window and the
  drain against the output lengths the window's requests asked for, and
  each finished request's length against its own, exact.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, traffic



# ------------------------------------------------------------------ program
def build(r: common.Run, prog: Dict[str, Any], tracer):
    """The engine under test, built through the program's public API over
    the weight buffers `prog` (the reference's `to_program` layout)."""
    from repro.core.split import SplitModel
    from repro.runtime import WireSpec
    from repro.serve import PagedServeConfig, PagedServeEngine, TenantBank

    cfg, sv = r.config, r.config["serve"]
    model = SplitModel(*common.program_model(cfg), WireSpec.make(cfg["wire"]))
    return PagedServeEngine(
        model, prog["shared"], TenantBank(prog["tails"], prog["prompts"]),
        PagedServeConfig(n_slots=sv["n_slots"], max_seq=sv["max_seq"],
                         page_size=sv["page_size"],
                         decode_block=sv["decode_block"], impl=sv["impl"],
                         donate=sv["donate"]),
        collect_logits=True, tracer=tracer)


def make_weights(r: common.Run):
    """The seed's weights, made on the device in one jitted call."""
    return jax.block_until_ready(jax.jit(
        lambda k: r.ref.make_weights(r.config, k))(common.seed_key(r.seed)))


def serve_checks(r: common.Run, engine) -> List[Any]:
    """Set-up: the check requests, one per slot, served to the end
    together; then one more alone, of `decode_block` output tokens, whose
    decode walks every smaller power-of-two bucket (7 -> 4, 2, 1), so that
    every decode program the window can dispatch has run."""
    from repro.serve import Request
    mix, sv = r.traffic, r.config["serve"]
    reqs = [Request(rid=i, tenant=mix["tenant"], tokens=tokens, max_new=n)
            for i, (tokens, n) in enumerate(traffic.check_requests(
                mix, r.seed, r.config["vocab_size"], sv["n_slots"]))]
    last = reqs[-1].tokens[: min(mix["prompt_lengths"])]
    done = []
    for batch in (reqs, [Request(rid=len(reqs), tenant=mix["tenant"],
                                 tokens=last, max_new=sv["decode_block"])]):
        for q in batch:
            if not engine.submit(q):
                raise RuntimeError(f"check request {q.rid} refused")
        while not engine.idle:
            done.extend(engine.step())
    return sorted(done, key=lambda f: f.req.rid)


def closed_window(r: common.Run, engine, first_rid: int) -> Dict[str, Any]:
    """The measured window: a closed loop of the mix's callers, then the
    drain of the requests still in flight."""
    from repro.serve import Request
    mix = r.traffic
    streams = traffic.closed_loop(mix, r.seed, r.config["vocab_size"])
    sent: Dict[int, Tuple[int, int, int]] = {}   # rid -> caller, p, n
    owner: Dict[int, int] = {}                   # rid in flight -> caller
    idle = list(range(mix["callers"]))
    in_window: List[Any] = []
    times: List[float] = []
    refused = 0
    rid = first_rid
    seconds = r.window_seconds()
    tracer = engine.tracer
    if tracer.enabled:
        tracer.drain()
    with r.window() as win:
        t0 = last = time.perf_counter()
        count0 = engine.tokens_out
        while last - t0 < seconds:
            for c in idle:
                tokens, n = next(streams[c])
                if engine.submit(Request(rid=rid, tenant=mix["tenant"],
                                         tokens=tokens, max_new=n)):
                    sent[rid], owner[rid] = (c, len(tokens), n), c
                else:
                    refused += 1
                rid += 1
            idle = [c for c in idle if c not in owner.values()]
            with jax.profiler.TraceAnnotation("bench.step"):
                done = engine.step()
            now = time.perf_counter()
            times.append(now - last)
            last = now
            for f in done:
                in_window.append(f)
                idle.append(owner.pop(f.req.rid))
        served = engine.tokens_out - count0
    elapsed = last - t0
    records = tracer.drain() if tracer.enabled else []
    drained: List[Any] = []
    t1 = time.perf_counter()
    while not engine.idle and time.perf_counter() - t1 < mix["drain_seconds"]:
        drained.extend(engine.step())
    return {"elapsed": elapsed, "tokens": served, "times": times,
            "sent": sent, "refused": refused, "in_window": in_window,
            "drained": drained, "drain_s": time.perf_counter() - t1,
            "counted": engine.tokens_out - count0,
            "compiles": r.clock.compiles - win.compiles_before
            if r.clock else 0,
            "trace": win.reduced,
            "record": decode_record(records, sent,
                                    r.config["split"]["prompt_len"])}


def decode_record(records, sent, prompt_len: int) -> Dict[str, Any]:
    """The window's decode iterations from the flight recorder's records:
    per iteration [active slots, distinct tenants among them, positions
    their caches hold], and the prefill lengths (soft prompt included).
    Each dispatch of n iterations serves an active slot min(n, tokens it
    still owes); iteration t of a slot that has served g tokens of a
    p-token prompt attends over prompt_len + p + g + t positions."""
    slots: Dict[int, List[int]] = {}     # slot -> [rid, served, p, n, tenant]
    iterations: List[List[int]] = []
    prefills: List[int] = []
    mismatches = 0
    for rec in sorted(records, key=lambda x: x["seq"]):
        a = rec.get("attrs", {})
        if rec["name"] == "serve.admit":
            _, p, n = sent[a["rid"]]
            slots[a["slot"]] = [a["rid"], 1, p, n, a["tenant"]]
            prefills.append(prompt_len + p)
        elif rec["name"] == "serve.decode":
            k = a["n_tokens"]
            for t in range(k):
                live = [s for s in slots.values() if s[3] - s[1] > t]
                iterations.append([len(live), len({s[4] for s in live}),
                                   sum(prompt_len + s[2] + s[1] + t
                                       for s in live)])
            mismatches += int(sum(s[3] > s[1] for s in slots.values())
                              != a["active"])
            for s in slots.values():
                s[1] += min(k, s[3] - s[1])
        elif rec["name"] == "serve.retire":
            slots = {k: s for k, s in slots.items() if s[0] != a["rid"]}
    return {"iterations": iterations, "prefills": prefills,
            "active_mismatches": mismatches}


# ---------------------------------------------------------------- reference
def precision(name: str):
    """`jax.lax.Precision` by its name in a configuration."""
    return getattr(jax.lax.Precision, name.upper())


def _reference_gaps(w, tokens, lo, n, served, prog, *, ref, cfg, prec,
                    with_logits, control):
    """One request's sums from the reference's logits, at matmul precision
    `prec`, at the rows that chose its served tokens: `scale` (largest
    |logit|), `gap_sum` / `gap_max` (the sum and the widest of the gaps by
    which a served token's logit lies below the row's best), `err_sq` /
    `err_max` (the sum of squares and the largest of program - reference),
    and with `control` the same of the bfloat16 control, whose tokens are
    the ones it puts first."""
    rows_at = lambda logits: logits[jnp.minimum(
        lo + jnp.arange(served.shape[0]), logits.shape[0] - 1)]
    valid = jnp.arange(served.shape[0]) < n
    rows = rows_at(ref.forward(w, tokens, cfg, precision=prec))
    masked = lambda x: jnp.where(valid[:, None], x, 0.0)
    gaps = lambda picked: jnp.where(valid, rows.max(-1) - jnp.take_along_axis(
        rows, picked[:, None], 1)[:, 0], 0.0)
    g = gaps(served)
    out = {"scale": jnp.max(masked(jnp.abs(rows))), "gap_sum": g.sum(),
           "gap_max": g.max()}
    if with_logits:
        d = masked(prog - rows)
        out.update(err_sq=jnp.sum(d * d), err_max=jnp.max(jnp.abs(d)))
    if control:
        d = masked(rows_at(ref.forward(w, tokens, cfg, jnp.bfloat16,
                                       precision=prec)) - rows)
        g = gaps(jnp.argmax(rows + d, -1))
        out.update(control_err_sq=jnp.sum(d * d),
                   control_err_max=jnp.max(jnp.abs(d)),
                   control_gap_sum=g.sum(), control_gap_max=g.max())
    return out


def reference_gaps(r: common.Run, w, finished, *, prec=None, control=False
                   ) -> List[Dict[str, float]]:
    """`_reference_gaps` of each finished request, one at a time, at one
    padded length: the prompt and the served tokens but the last, zeros
    after them. `prec` defaults to the configuration's matmul precision."""
    cfg, mix = r.config, r.traffic
    prec = precision(cfg["matmul_precision"]) if prec is None else prec
    P = cfg["split"]["prompt_len"]
    T = cfg["serve"]["max_seq"] - P
    N = max(mix["output_lengths"])
    out = []
    for f in finished:
        p, toks = len(f.req.tokens), np.asarray(f.tokens, np.int32)
        n = len(toks)
        seq = np.zeros(T, np.int32)
        seq[:p], seq[p:p + n - 1] = f.req.tokens, toks[:-1]
        served = np.zeros(N, np.int32)
        served[:n] = toks
        prog = None
        if f.logits is not None:
            prog = np.zeros((N, cfg["vocab_size"]), np.float32)
            prog[:n] = f.logits
        got = common.jit_once(_reference_gaps, ref=r.ref, cfg=cfg,
                              prec=prec, with_logits=prog is not None,
                              control=control)(
            w, jnp.asarray(seq), jnp.int32(P + p - 1), jnp.int32(n),
            jnp.asarray(served), prog)
        out.append(dict({k: float(v) for k, v in got.items()}, n=n))
    return out


def margin_sample(r: common.Run, finished) -> List[Any]:
    """Up to `margin_sample` of the window's finished requests, drawn from
    the seed, with the longest (prompt and output) among them."""
    k = min(r.traffic["margin_sample"], len(finished))
    if not k:
        return []
    pick = list(traffic.rng(r.seed, 7).choice(len(finished), k,
                                              replace=False))
    longest = max(range(len(finished)), key=lambda i: (
        len(finished[i].req.tokens) + finished[i].req.max_new, -i))
    if longest not in pick:
        pick[-1] = longest
    return [finished[i] for i in sorted(pick)]


def wire_count(cfg, finished) -> float:
    """Bytes each boundary carries for the finished requests, fp32 rows:
    the prefill's soft prompt and prompt, then one row per decoded token
    after the prefill's own."""
    if cfg["wire"] != "fp32":
        raise ValueError(f"wire {cfg['wire']!r}: the count knows fp32 only")
    rows = sum(cfg["split"]["prompt_len"] + len(f.req.tokens)
               + f.req.max_new - 1 for f in finished)
    return 4.0 * cfg["hidden_size"] * rows


def gap_readings(checked, sampled, *, vocab: int, who: str = ""
                 ) -> Dict[str, float]:
    """From the reference's sums for the check requests (`checked`, with
    the program's logits) and the window's sample (`sampled`), each over
    its request's largest |logit|:

    - logits: the root mean square of program - reference over every
      logit of the check requests' served rows;
    - margin: the mean gap below the row's best of the served tokens of
      the check requests and the sample;
    - logits_max, margin_max: the largest logit gap and the widest gap,
      read but not compared.

    `who` "control_" reads the control's sums in the program's place."""
    both = checked + sampled
    rows = sum(g["n"] for g in checked)
    return {"logits": (sum(g[who + "err_sq"] / g["scale"] ** 2
                           for g in checked) / (rows * vocab)) ** 0.5,
            "margin": sum(g[who + "gap_sum"] / g["scale"] for g in both)
            / sum(g["n"] for g in both),
            "logits_max": max(g[who + "err_max"] / g["scale"]
                              for g in checked),
            "margin_max": max(g[who + "gap_max"] / g["scale"] for g in both)}


def exact_readings(metered, expected, counted, window_reqs, finished
                   ) -> Dict[str, float]:
    """wire_bytes: the metered bytes' gap to the count from shapes, worst
    boundary; tokens_counted: the token counter's gap to the output
    lengths the window's requests asked for, plus each finished request's
    gap to its own."""
    want = sum(n for _, _, n in window_reqs)
    return {"wire_bytes": max(abs(metered[k] - expected)
                              for k in ("head_body", "body_tail")),
            "tokens_counted": float(abs(counted - want) + sum(
                abs(len(f.tokens) - f.req.max_new) for f in finished))}


COMPARED = ("logits", "margin", "wire_bytes", "tokens_counted")


# ------------------------------------------------------------------- a run
def serve(r: common.Run, w, tracer) -> Dict[str, Any]:
    """Everything a run does on the program's side: engine, check
    requests, window and drain. Frees the engine before it returns."""
    engine = build(r, r.ref.to_program(w, r.config), tracer)
    r.plant(engine)
    r.mark("engine")
    checks = serve_checks(r, engine)
    engine.collect_logits = False
    r.mark("check_requests")
    setup_s = time.time() - r.t_start
    compile_s = r.clock.seconds if r.clock else 0.0
    win = closed_window(r, engine, first_rid=len(checks))
    device = common.device_record(r.devices) if r.devices else {}
    memory = common.memory_note(r.devices) if r.devices else ""
    metered = engine.stats([], 0.0)["wire_bytes"]
    del engine
    gc.collect()
    return dict(win, checks=checks, setup_s=setup_s, compile_s=compile_s,
                device=device, memory=memory, metered=metered)


def compare(r: common.Run, w, s: Dict[str, Any], *, prec=None,
            control=False):
    """The readings of the program and, with `control`, of the control."""
    finished = s["checks"] + s["in_window"] + s["drained"]
    sample = margin_sample(r, s["in_window"])
    gaps = reference_gaps(r, w, s["checks"] + sample, prec=prec,
                          control=control)
    k, V = len(s["checks"]), r.config["vocab_size"]
    got = dict(gap_readings(gaps[:k], gaps[k:], vocab=V), **exact_readings(
        s["metered"], wire_count(r.config, finished), s["counted"],
        list(s["sent"].values()), finished))
    ctl = (gap_readings(gaps[:k], gaps[k:], vocab=V, who="control_")
           if control else None)
    return got, ctl, len(sample)


def run(r: common.Run) -> Dict[str, Any]:
    from repro.obs import make_tracer
    w = make_weights(r)
    r.mark("weights")
    s = serve(r, w, make_tracer("step" if r.trace else "off"))
    t_ref = time.perf_counter()
    got, _, n_sample = compare(r, w, s)
    t_ref = time.perf_counter() - t_ref
    checks = [{"name": k, "value": got[k], "limit": r.limits[k]["limit"]}
              for k in COMPARED]
    failed = s["refused"] + len(s["sent"]) - len(s["in_window"]) \
        - len(s["drained"])
    times = s["times"] or [0.0]
    rec = s["record"]
    return {
        "setup_s": s["setup_s"], "compile_s": s["compile_s"],
        "end_to_end": {"tokens_per_s": s["tokens"] / s["elapsed"]},
        "attempted": len(s["sent"]) + s["refused"], "failed": failed,
        "window": rec, "device": s["device"], "trace": s["trace"],
        "checks": checks,
        "notes": [f"window: {s['tokens']} tokens in {s['elapsed']:.6f} s "
                  f"over {len(s['times'])} engine steps, "
                  f"{len(s['in_window'])} requests finished in it, "
                  f"{s['compiles']} compiles inside it; step s median "
                  f"{np.median(times):.6f} max {max(times):.6f}; "
                  f"drain {len(s['drained'])} requests in "
                  f"{s['drain_s']:.3f} s",
                  f"decode record: {len(rec['iterations'])} iterations, "
                  f"{len(rec['prefills'])} prefills, "
                  f"{rec['active_mismatches']} dispatches whose active "
                  f"count differs from serve.decode's",
                  s["memory"],
                  f"reference: {t_ref:.3f} s for {len(s['checks'])} check "
                  f"and {n_sample} window requests; read, not compared: "
                  f"logits_max {got['logits_max']!r}, margin_max "
                  f"{got['margin_max']!r}; metered wire {s['metered']}"],
    }


# ------------------------------------------------------------- calibration
def calibration(r: common.Run, faults: bool = False):
    """(who, readings) for `bench/calibrate.py`: the program as a run
    reads it (window of `r.seconds`), the bfloat16 control on the same
    requests, both also against the reference at 'highest' precision
    (`_exact`), and, with `faults`, each planted fault read through the
    check requests at the cell's own size."""
    from repro.obs import NOOP
    w = make_weights(r)
    s = serve(r, w, NOOP)
    for tag, prec in (("", None), ("_exact", jax.lax.Precision.HIGHEST)):
        got, ctl, _ = compare(r, w, s, prec=prec, control=True)
        yield "program" + tag, got
        yield "control" + tag, ctl
    del s
    gc.collect()
    for name, plant in (FAULTS.items() if faults else ()):
        engine = build(r, r.ref.to_program(w, r.config), NOOP)
        plant(engine)
        checks = serve_checks(r, engine)
        metered = engine.stats([], 0.0)["wire_bytes"]
        del engine
        gc.collect()
        yield name, dict(
            gap_readings(reference_gaps(r, w, checks), [],
                         vocab=r.config["vocab_size"]),
            **exact_readings(metered, wire_count(r.config, checks), 0, [],
                             checks))


# ------------------------------------------------------------------ faults
def _cache_write_skipped(engine):
    """The tail layer's decode K/V write into the page pool is skipped."""
    engine.model.paged_scatter_token = lambda pool, dense, tables, pos: pool


def _state_unchanged(engine):
    """Each decode step hands back the page pool it was given: no layer's
    K/V write of a decoded token lands."""
    model = engine.model
    head_fwd, body_fwd = model.head_fwd, model.body_fwd

    def head(head_p, prompt, batch, *, mode="train", cache=None, **kw):
        out = head_fwd(head_p, prompt, batch, mode=mode, cache=cache, **kw)
        return dict(out, cache=cache) if mode == "decode" else out

    def body(body_p, smashed, head_out, *, cache=None):
        out = body_fwd(body_p, smashed, head_out, cache=cache)
        return dict(out, cache=cache) if head_out["mode"] == "decode" \
            else out
    model.head_fwd, model.body_fwd = head, body
    _cache_write_skipped(engine)


def _prompt_dropped(engine):
    """The prefill leaves out the tenant's soft prompt."""
    head_fwd = engine.model.head_fwd

    def head(head_p, prompt, batch, **kw):
        return head_fwd(head_p, None, batch, **kw)
    engine.model.head_fwd = head


def _position_off_by_one(engine):
    """Decode runs the head and the body one position before the token's:
    each token takes the previous one's rotary position and overwrites its
    K/V in those layers."""
    head_fwd = engine.model.head_fwd

    def head(head_p, prompt, batch, *, mode="train", **kw):
        if mode == "decode":
            batch = dict(batch, pos=batch["pos"] - 1)
        return head_fwd(head_p, prompt, batch, mode=mode, **kw)
    engine.model.head_fwd = head


def _token_altered(engine):
    """One token of every request is altered where the engine hands it
    out: its sixth, or its last if it has fewer."""
    step, V = engine.step, engine.model.cfg.vocab_size

    def altered():
        done = step()
        for f in done:
            i = min(5, len(f.tokens) - 1)
            f.tokens = f.tokens.copy()
            f.tokens[i] = (int(f.tokens[i]) + 1) % V
        return done
    engine.step = altered


FAULTS = {"cache_write_skipped": _cache_write_skipped,
          "state_unchanged": _state_unchanged,
          "prompt_dropped": _prompt_dropped,
          "position_off_by_one": _position_off_by_one,
          "token_altered": _token_altered}
