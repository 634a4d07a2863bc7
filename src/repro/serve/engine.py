"""ServeEngine: multi-tenant continuous-batching engine for split inference.

Slot lifecycle (see ARCHITECTURE.md §Serving engine):

    queue ──admit──> FREE slot ──prefill──> ACTIVE ──max_new reached──> FREE
      ^                (batch=1, tenant         (joins the batched
      └ admission       tail+prompt, cache       decode every step)
        control         scattered into the
        (max_queue)     slot's cache rows)

The shared KV cache is `SplitModel.init_cache(n_slots, ...)`: batch row i
IS slot i, owned by at most one in-flight request. Scheduling interleaves
prefill and decode: each `step()` admits up to `prefills_per_step` queued
requests into free slots (a batch=1 prefill each, scattered via
`cache_write_slot`), then runs ONE batched decode step over all slots —
requests join and leave mid-flight without ever draining the batch.

Per-tenant personalization: every request carries a tenant id; decode
gathers that tenant's tail from the `TenantBank` per slot (vmapped tail,
one compiled step for heterogeneous tenants) and prefill injects the
tenant's soft prompt. The frozen head/body are shared by everyone.

All smashed tensors cross the `WireSpec` boundaries; the engine's
`TrafficMeter` holds measured bytes (decode metered per occupied row),
cross-checked against `core.comm.serve_comm_breakdown` in tests and CI.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.split import SplitModel
from repro.obs.trace import NOOP
from repro.runtime.boundary import BOUNDARY_NAMES
from repro.runtime.meter import TrafficMeter
from repro.serve.bank import TenantBank
from repro.serve.steps import (make_batched_decode_step,
                               make_multi_decode_step, make_step_shardings,
                               make_tenant_prefill_step)
from repro.serve.workload import Request
from repro.sharding.rules import report_fallbacks

_DONATION_WARNING_FILTERED = False


def _quiet_cpu_donation_warning() -> None:
    """On a backend without donation jax falls back to a copy and warns
    once per compile. That is the engine's EXPECTED state on CPU (tests,
    CI), so suppress exactly that diagnostic — once per process, and only
    when a donating engine is actually constructed on such a backend
    (never at import, never on TPU/GPU, no duplicate filter entries)."""
    global _DONATION_WARNING_FILTERED
    if not _DONATION_WARNING_FILTERED and jax.default_backend() == "cpu":
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        _DONATION_WARNING_FILTERED = True


@dataclass(frozen=True)
class ServeConfig:
    n_slots: int = 8          # concurrent requests (shared-cache batch)
    max_seq: int = 128        # per-slot KV window (prompt + soft prompt
    #                           + generated tokens must fit)
    max_queue: int = 64       # admission control: pending-request cap
    prefills_per_step: int = 2  # joins per engine step (prefill/decode mix)
    decode_block: int = 1     # decode fast path: tokens per dispatch — one
    #                           lax.scan of up to this many decode steps per
    #                           engine step (power-of-two buckets keep the
    #                           jit-cache count bounded); 1 = per-token
    donate: bool = True       # donate the KV-cache pytrees into the jitted
    #                           steps so they update in place (no-op copy
    #                           fallback on backends without donation)
    dtype: Any = jnp.float32
    impl: str = "ref"


@dataclass
class _SlotState:
    req: Request
    next_pos: int             # absolute position of the next decode token
    tokens: List[int] = field(default_factory=list)
    logits: List[np.ndarray] = field(default_factory=list)
    t_submit: float = 0.0


@dataclass
class Finished:
    req: Request
    tokens: np.ndarray                      # (max_new,) generated ids
    latency_s: float
    logits: Optional[np.ndarray] = None     # (max_new, V) if collected


class ServeEngine:
    def __init__(self, model: SplitModel, shared_params, bank: TenantBank,
                 cfg: ServeConfig, *, collect_logits: bool = False,
                 mesh=None, tracer=None):
        if model.cfg.arch_type in ("vit", "audio", "vlm") \
                or model.cfg.encoder is not None:
            raise ValueError(
                f"{model.cfg.name}: the serving engine decodes token "
                f"streams; arch_type {model.cfg.arch_type!r} has no "
                f"token decode loop")
        self.model = model
        self.shared = {"head": shared_params["head"],
                       "body": shared_params["body"]}
        self.bank = bank
        self.cfg = cfg
        self.collect_logits = collect_logits
        # flight recorder (repro.obs): observation only — the default NOOP
        # records nothing; byte-carrying records appear ONLY where the
        # host already folds bytes (the meter flush), never per token
        self.tracer = tracer if tracer is not None else NOOP
        self.meter = TrafficMeter()
        self.meter.attach_tracer(self.tracer)

        S = cfg.n_slots
        self.cache = model.init_cache(S, seq_len=cfg.max_seq,
                                      dtype=jnp.float32)
        self._blank = model.blank_slot_cache(cfg.max_seq,
                                             dtype=jnp.float32)
        self._tokens = np.zeros((S,), np.int32)     # next input per slot
        self._pos = np.zeros((S,), np.int32)
        self._tenants = np.zeros((S,), np.int32)
        self._slots: List[Optional[_SlotState]] = [None] * S
        self._free: List[int] = list(range(S))      # free-list (LIFO)
        self._queue: List[Request] = []
        self._t_enqueue: Dict[int, float] = {}      # rid -> submit time

        # The blank prefill cache is REUSED every admission, so the prefill
        # step donates nothing; the shared cache pytree is donated into the
        # decode steps and the slot scatter so it updates in place.
        donate = (6,) if cfg.donate else ()
        if cfg.donate:
            _quiet_cpu_donation_warning()
        # mesh: run the steps TENSOR-PARALLEL on a (data, model) mesh —
        # frozen head/body sharded over 'model' (head-parallel attention,
        # d_ff-parallel MLP), KV slots over 'data', kv-heads over 'model';
        # per-device body+cache HBM drops ~1/|model| while decode math
        # stays bit-comparable (tests pin logits dense-vs-TP)
        self._mesh = mesh
        self._step_sh = None
        pf_kw: Dict[str, Any] = {}
        self._dec_kw: Dict[str, Any] = {}
        ws_kw: Dict[str, Any] = {}
        if mesh is not None:
            sh = make_step_shardings(mesh, self.shared, cache=self.cache,
                                     blank=self._blank)
            self._report_fallbacks()
            self._step_sh = sh
            r = sh["repl"]
            self.shared = jax.device_put(self.shared, sh["shared"])
            self.cache = jax.device_put(self.cache, sh["cache"])
            self._blank = jax.device_put(self._blank, sh["blank"])
            pf_kw = dict(
                in_shardings=(sh["shared"], r, r, r, sh["blank"]),
                out_shardings=(r, r, sh["blank"], r))
            self._dec_kw = dict(
                in_shardings=(sh["shared"], r, r, r, r, r, sh["cache"]),
                out_shardings=(r, r, sh["cache"], r))
            ws_kw = dict(in_shardings=(sh["cache"], sh["blank"], r),
                         out_shardings=sh["cache"])
        self._prefill = jax.jit(make_tenant_prefill_step(
            model, impl=cfg.impl, dtype=cfg.dtype), **pf_kw)
        self._decode = jax.jit(make_batched_decode_step(
            model, impl=cfg.impl, dtype=cfg.dtype), donate_argnums=donate,
            **self._dec_kw)
        self._multi: Dict[int, Any] = {}    # decode_block bucket -> jit
        self._write_slot = (
            model.jit_slot_writer(donate=cfg.donate) if mesh is None
            else jax.jit(model.cache_write_slot,
                         donate_argnums=(0,) if cfg.donate else (),
                         **ws_kw))

        # measured wire bytes accumulate ON DEVICE (traced scalars chained
        # with jnp.add, never synced per token) and fold into the host-side
        # meter once per flush — stats()/reset_stats() — instead of forcing
        # a device->host transfer every decode step.
        self._wire_acc = self._zero_wire()

        # step accounting
        self.step_idx = 0
        self.decode_steps = 0
        self.prefill_count = 0
        self.rejected = 0
        self.tokens_out = 0
        self._occupancy_sum = 0.0

    def _report_fallbacks(self, context: str = "serve.steps") -> None:
        """Surface any divisibility fallbacks the spec builders recorded —
        a kv-head count that does not divide 'model' means this mesh is
        silently replicating what it was sized to shard. Routed through
        the structured event log when a tracer is attached; the warning
        stays either way."""
        report_fallbacks(context, self.tracer)

    # -------------------------------------------------------------- wire
    @staticmethod
    def _zero_wire() -> Dict[str, jnp.ndarray]:
        return {name: jnp.float32(0.0) for name in BOUNDARY_NAMES}

    def _absorb_wire(self, wb) -> None:
        """Chain a step's byte counters onto the device-side accumulator —
        a lazy device add, NO host sync (the old per-token float() absorb
        blocked the decode loop on a device->host transfer every step)."""
        self._wire_acc = {k: self._wire_acc[k] + wb[k]
                         for k in self._wire_acc}

    def _flush_wire(self) -> None:
        """Fold the device-side accumulator into the host meter (one sync
        per flush — called from stats()/reset_stats(), not per token)."""
        vals = {k: float(v) for k, v in self._wire_acc.items()}
        if any(vals.values()):
            self.meter.absorb(vals)
        self._wire_acc = self._zero_wire()

    # ------------------------------------------------------------- intake
    def _window_check(self, req: Request) -> None:
        """Reject requests that cannot fit a slot's KV window. Subclasses
        with coarser-grained capacity (the paged engine rounds up to whole
        pages) override this."""
        total = len(req.tokens) + self.model.split.prompt_len + req.max_new
        if total > self.cfg.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt({len(req.tokens)}) + soft "
                f"prompt({self.model.split.prompt_len}) + "
                f"new({req.max_new}) = {total} exceeds the slot window "
                f"{self.cfg.max_seq}")

    def submit(self, req: Request) -> bool:
        """Admission control: False (rejected) once the queue is full."""
        self._window_check(req)
        if req.tenant >= self.bank.n_tenants:
            raise ValueError(f"request {req.rid}: unknown tenant "
                             f"{req.tenant} (bank has {self.bank.n_tenants})")
        if len(self._queue) >= self.cfg.max_queue:
            self.rejected += 1
            self.tracer.event("serve.reject", level=2, rid=req.rid,
                              tenant=req.tenant)
            return False
        self._t_enqueue[req.rid] = time.perf_counter()
        self._queue.append(req)
        self.tracer.event("serve.submit", level=2, rid=req.rid,
                          tenant=req.tenant, prompt_len=len(req.tokens),
                          max_new=req.max_new)
        return True

    @property
    def n_active(self) -> int:
        return self.cfg.n_slots - len(self._free)

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def idle(self) -> bool:
        return self.n_active == 0 and not self._queue

    # ------------------------------------------------------------ prefill
    def _admit_one(self, req: Request) -> Optional[Finished]:
        slot = self._free.pop()
        prompt_np = np.asarray(req.tokens, np.int32)[None]
        batch = {"tokens": jnp.asarray(prompt_np)}
        tail = self.bank.tail(req.tenant)
        prompt = self.bank.prompt(req.tenant)
        with self.tracer.span("serve.prefill", rid=req.rid,
                              tenant=req.tenant, slot=slot,
                              prompt_len=len(req.tokens)):
            tok, logits, slot_cache, wb = self._prefill(
                self.shared, tail, prompt, batch, self._blank)
            self.cache = self._write_slot(self.cache, slot_cache,
                                          jnp.int32(slot))
        self._absorb_wire(wb)
        self.prefill_count += 1
        self.tokens_out += 1

        st = _SlotState(req=req,
                        t_submit=self._t_enqueue.pop(
                            req.rid, time.perf_counter()),
                        next_pos=len(req.tokens)
                        + self.model.split.prompt_len)
        st.tokens.append(int(tok[0]))
        if self.collect_logits:
            st.logits.append(np.asarray(logits[0]))
        if req.max_new <= 1:
            self._release_slot(slot)
            return self._finish(st)
        self._slots[slot] = st
        self._tokens[slot] = int(tok[0])
        self._pos[slot] = st.next_pos
        self._tenants[slot] = req.tenant
        return None

    def _finish(self, st: _SlotState) -> Finished:
        # retirement attrs stay deterministic — token COUNTS, never the
        # wall-clock latency (same-seed traces must compare equal)
        self.tracer.event("serve.retire", rid=st.req.rid,
                          tenant=st.req.tenant, n_tokens=len(st.tokens))
        return Finished(
            req=st.req, tokens=np.asarray(st.tokens, np.int32),
            latency_s=time.perf_counter() - st.t_submit,
            logits=(np.stack(st.logits) if st.logits else None))

    # -------------------------------------------------------------- step
    def _decode_bucket(self, max_remaining: int) -> int:
        """Tokens to decode in one dispatch: the largest power of two <=
        min(decode_block, max slot budget) — power-of-two buckets bound the
        number of compiled multi-step variants at log2(decode_block)."""
        n = min(self.cfg.decode_block, max_remaining)
        return 1 << (max(1, n).bit_length() - 1)

    def _get_multi(self, n_steps: int):
        fn = self._multi.get(n_steps)
        if fn is None:
            donate = (6,) if self.cfg.donate else ()
            fn = jax.jit(make_multi_decode_step(
                self.model, n_steps, impl=self.cfg.impl,
                dtype=self.cfg.dtype, with_logits=self.collect_logits),
                donate_argnums=donate, **self._dec_kw)
            self._multi[n_steps] = fn
        return fn

    def _can_admit(self, req: Request) -> bool:
        """Head-of-line admission gate beyond free slots (the paged engine
        waits here when the page pool cannot cover the request)."""
        return True

    def _admit_from_queue(self, done: List[Finished]) -> None:
        """Admit up to `prefills_per_step` queued requests into free slots
        (head-of-line order; `_can_admit` can stall the queue without
        dropping it)."""
        admitted = 0
        while (self._queue and self._free
               and admitted < self.cfg.prefills_per_step):
            if not self._can_admit(self._queue[0]):
                break
            fin = self._admit_one(self._queue.pop(0))
            admitted += 1
            if fin is not None:
                done.append(fin)

    def _dispatch_decode(self, remaining: np.ndarray, n_eff: int):
        """Run one decode dispatch (single-token or scanned multi-token)
        over the engine's cache state; returns ((n_eff, S) tokens,
        (n_eff, S, V) logits or None, wire bytes). Subclasses swap the
        cache representation here."""
        if n_eff <= 1:
            toks, logits, self.cache, wb = self._decode(
                self.shared, self.bank.tails,
                jnp.asarray(self._tenants), jnp.asarray(self._tokens),
                jnp.asarray(self._pos),
                jnp.asarray(remaining > 0, jnp.float32), self.cache)
            return toks[None], logits[None], wb         # (1, S[, V])
        toks, logits, self.cache, wb = self._get_multi(n_eff)(
            self.shared, self.bank.tails,
            jnp.asarray(self._tenants), jnp.asarray(self._tokens),
            jnp.asarray(self._pos), jnp.asarray(remaining), self.cache)
        return toks, logits, wb

    def _release_slot(self, slot: int) -> None:
        """Return a retired slot to the free list (the paged engine also
        releases the slot's pages and scrubs its block table)."""
        self._free.append(slot)

    def step(self) -> List[Finished]:
        """One engine step: admit up to `prefills_per_step` queued requests
        into free slots, then one batched decode over every occupied slot —
        a single token, or (decode fast path) up to `decode_block` tokens
        in one scanned dispatch, with retirement deferred to scan exit.
        Returns the requests that completed during this step."""
        done: List[Finished] = []
        self._admit_from_queue(done)

        remaining = np.array(
            [0 if s is None else s.req.max_new - len(s.tokens)
             for s in self._slots], np.int32)
        if not remaining.any():
            self.step_idx += 1
            return done
        n_eff = self._decode_bucket(int(remaining.max()))
        with self.tracer.span("serve.decode", level=2, step=self.step_idx,
                              n_tokens=n_eff,
                              active=int((remaining > 0).sum())):
            toks, logits, wb = self._dispatch_decode(remaining, n_eff)
        self._absorb_wire(wb)
        self.decode_steps += n_eff
        for t in range(n_eff):
            self._occupancy_sum += ((remaining > t).sum()
                                    / self.cfg.n_slots)
        tok_np = np.asarray(toks)
        logits_np = np.asarray(logits) if self.collect_logits else None
        for slot, st in enumerate(self._slots):
            if st is None:
                continue
            take = min(n_eff, int(remaining[slot]))
            for t in range(take):
                st.tokens.append(int(tok_np[t, slot]))
                if self.collect_logits:
                    st.logits.append(logits_np[t, slot])
                st.next_pos += 1
            self.tokens_out += take
            self._tokens[slot] = tok_np[take - 1, slot]
            self._pos[slot] = st.next_pos
            if len(st.tokens) >= st.req.max_new:
                done.append(self._finish(st))
                self._slots[slot] = None
                self._release_slot(slot)
        self.step_idx += n_eff
        return done

    # ------------------------------------------------------------- reset
    def reset_stats(self) -> None:
        """Zero the run counters and the meter (engine must be idle): one
        engine can then serve several measured traces without cross-run
        accumulation, and arrival schedules replay from step 0 while the
        jit caches stay warm (benchmarks warm up this way)."""
        if not self.idle:
            raise RuntimeError("reset_stats with requests in flight")
        self.meter = TrafficMeter()
        self.meter.attach_tracer(self.tracer)
        self._wire_acc = self._zero_wire()
        self.step_idx = 0
        self.decode_steps = 0
        self.prefill_count = 0
        self.rejected = 0
        self.tokens_out = 0
        self._occupancy_sum = 0.0

    # ------------------------------------------------------------ driver
    def run(self, requests: Sequence[Request], *,
            max_steps: int = 100_000,
            on_step=None) -> Dict[str, Any]:
        """Drive a full (arrival-sorted) request trace to completion.
        Deterministic in (engine seed state, trace): scheduling decisions
        depend only on arrival steps and queue/slot order. `on_step`
        (engine_step_idx -> None) fires after every step — the launcher's
        periodic-metrics hook; it must not mutate the engine."""
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        finished: List[Finished] = []
        t0 = time.perf_counter()
        i = 0
        while (i < len(pending) or not self.idle):
            while i < len(pending) and pending[i].arrival <= self.step_idx:
                self.submit(pending[i])
                i += 1
            finished.extend(self.step())
            if on_step is not None:
                on_step(self.step_idx)
            if self.step_idx > max_steps:
                raise RuntimeError(f"workload did not drain in "
                                   f"{max_steps} engine steps")
        wall = time.perf_counter() - t0
        return self.stats(finished, wall)

    def live_stats(self) -> Dict[str, Any]:
        """Zero-arg counters for mid-run polling (the MetricsRegistry
        source). Unlike `stats`, needs no finished list or wall clock and
        never forces a device sync — the wire numbers reflect the last
        flush, not in-flight accumulators."""
        return {
            "step_idx": self.step_idx,
            "rejected": self.rejected,
            "tokens_out": self.tokens_out,
            "decode_steps": self.decode_steps,
            "prefills": self.prefill_count,
            "occupancy": self._occupancy_sum / max(1, self.decode_steps),
            "wire_bytes": self.meter.as_dict(),
        }

    def stats(self, finished: List[Finished], wall_s: float,
              ) -> Dict[str, Any]:
        self._flush_wire()
        lat = sorted(f.latency_s for f in finished) or [0.0]

        def pct(p):
            return lat[min(len(lat) - 1, int(p * len(lat)))]

        return {
            "finished": finished,
            "n_finished": len(finished),
            "rejected": self.rejected,
            "tokens_out": self.tokens_out,
            "wall_s": wall_s,
            "tok_per_s": self.tokens_out / max(wall_s, 1e-9),
            "p50_latency_s": pct(0.50),
            "p99_latency_s": pct(0.99),
            "occupancy": (self._occupancy_sum
                          / max(1, self.decode_steps)),
            "decode_steps": self.decode_steps,
            "prefills": self.prefill_count,
            "wire_bytes": self.meter.as_dict(),
            "wire_per_token": self.meter.per_token(self.tokens_out),
        }
