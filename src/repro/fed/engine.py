"""FederatedEngine: population-scale driver for the SFPrompt protocol.

One object owns the full loop the launcher used to hand-roll:

    sample cohort (ClientSampler)  ->  gather data (Population, on the
    device where the dataset fits there)
    ->  simulate stragglers (RoundScheduler)  ->  train the cohort
    (SFPromptTrainer._round, vmapped K-axis intact)  ->  write back
    per-client state  ->  checkpoint.

and makes the whole thing RESUMABLE: `save()` writes params, the round
counter, the TrafficMeter totals, the sampler position, and the
population's per-client state into one atomic npz; `restore()` brings a
killed run back to a state from which every subsequent round — sampled
cohort, straggler plan, parameter update, metered bytes — is byte-identical
to the uninterrupted run (samplers and schedulers are pure functions of
(seed, round), so the round counter IS their PRNG position).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import load_latest, save_checkpoint
from repro.fed.population import Population
from repro.fed.sampler import ClientSampler
from repro.fed.scheduler import (FullParticipationScheduler, RoundPlan,
                                 RoundScheduler)
from repro.obs.trace import NOOP


class FederatedEngine:
    def __init__(self, trainer, population: Population,
                 sampler: ClientSampler,
                 scheduler: Optional[RoundScheduler] = None, *,
                 personalize_tails: bool = False, tracer=None):
        if sampler.n_clients != population.n_clients:
            raise ValueError(
                f"sampler over {sampler.n_clients} clients but population "
                f"has {population.n_clients}")
        self.trainer = trainer
        self.population = population
        self.sampler = sampler
        if scheduler is not None and not getattr(
                trainer, "supports_partial", False):
            raise ValueError(
                f"{type(trainer).__name__} trains its cohort synchronously "
                "and cannot honor a straggler plan — omit the scheduler "
                "(FL/SFL baselines always run full participation)")
        self.scheduler = scheduler or FullParticipationScheduler(
            seed=sampler.seed)
        if personalize_tails and not getattr(
                getattr(trainer, "pcfg", None), "return_client_trainable",
                False):
            raise ValueError(
                "personalize_tails=True needs a trainer built with "
                "ProtocolConfig(return_client_trainable=True) so per-client "
                "tails survive the round")
        self.personalize_tails = personalize_tails
        # flight recorder (repro.obs): the default NOOP records nothing
        self.tracer = tracer if tracer is not None else NOOP
        self.round_idx = 0
        self.state: Optional[Dict[str, Any]] = None
        self.cohort_history: list = []   # per-round sampled ids (this run)

    # --------------------------------------------------------------- state
    def init(self, key) -> None:
        self.state = self.trainer.init(key)
        self.round_idx = 0

    @property
    def params(self):
        return self.state["params"]

    # --------------------------------------------------------------- round
    def run_round(self) -> Tuple[RoundPlan, Dict[str, float]]:
        """Sample -> gather -> schedule -> train -> write back. Returns the
        straggler plan and the trainer metrics for the round.

        Each stage is a span of the engine's tracer: `fed.round` over
        `fed.sample`, `fed.gather` (attr `where`: "device" when the
        population's data is resident there, else "host"), `fed.put` (the
        cohort's copy to the device, a no-op after a device gather), the
        trainer's `sfprompt.round` and `fed.writeback`."""
        if self.state is None:
            raise RuntimeError("call init(key) or restore(ckpt_dir) first")
        tracer = self.tracer
        r = self.round_idx
        with tracer.span("fed.round", round=r) as round_span:
            with tracer.span("fed.sample"):
                cohort = self.sampler.sample(r)
                plan = self.scheduler.plan(cohort, r)
            round_span.set(cohort=len(cohort))
            with tracer.span("fed.gather",
                             where=self.population.where) as sp:
                gathered = self.population.gather(cohort)
                nbytes = int(sum(v.nbytes for v in gathered.values()))
                sp.set(bytes=nbytes)
            with tracer.span("fed.put", bytes=nbytes):
                data = {k: jnp.asarray(v) for k, v in gathered.items()}
            metrics = self._train(cohort, plan, data)
            with tracer.span("fed.writeback"):
                self._write_back(cohort, plan, r, metrics)
        return plan, metrics

    def _train(self, cohort, plan: RoundPlan, data) -> Dict[str, float]:
        if getattr(self.trainer, "supports_partial", False):
            part = plan.participation()
            # paper Eq. 3: FedAvg weighted by TRUE per-client sample counts
            # (pre-padding Dirichlet sizes), folded into the participation
            # weight — fedavg_partial normalizes, so only ratios matter
            part["aggregate"] = (part["aggregate"] *
                                 self.population.cohort_sizes(cohort)
                                 .astype(np.float32))
            part = {k: jnp.asarray(v) for k, v in part.items()}
            init_tails = None
            if self.personalize_tails:
                # each sampled client resumes from its OWN last tail
                # (global tail for the never-sampled); FedAvg still feeds
                # the shared global tail every round
                per_client = self.population.get_tails(
                    cohort, self.state["params"]["tail"])
                if per_client is not None:
                    init_tails = jax.tree.map(
                        lambda *xs: jnp.stack(
                            [jnp.asarray(x) for x in xs]), *per_client)
            self.state, metrics = self.trainer.round(self.state, data, part,
                                                     init_tails)
        else:
            # baselines (FL / SFL) predate partial participation: they run
            # the cohort synchronously and ignore the straggler plan
            self.state, metrics = self.trainer.round(self.state, data)
        return metrics

    def _write_back(self, cohort, plan: RoundPlan, r: int,
                    metrics: Dict[str, float]) -> None:
        if self.personalize_tails:
            per_client = getattr(self.trainer, "last_client_trainable", None)
            if per_client is not None:
                # survivors keep their own post-round tail (pre-FedAvg) —
                # the personalized-tail regime of the hetero plans
                active_ids = cohort[plan.aggregate > 0]
                pos = np.flatnonzero(plan.aggregate > 0)
                tails = jax.tree.map(lambda x: np.asarray(x)[pos],
                                     per_client["tail"])
                self.population.set_tails(active_ids, tails)

        active = plan.aggregate > 0
        self.population.record_participation(cohort[active], r)
        metrics["cohort/sampled"] = float(len(cohort))
        metrics["cohort/dropped"] = float(plan.dropped.sum())
        metrics["cohort/late"] = float(plan.late.sum())
        self.cohort_history.append(np.asarray(cohort))
        self.round_idx = r + 1

    # ------------------------------------------------------------- resume
    def _trainer_fingerprint(self) -> np.int64:
        """CRC of the trainer's hyperparameter dataclasses — checkpointed
        so a resume with changed --lr/--gamma/--prompt-len/... fails
        loudly like the sampler/scheduler/population mismatches do.
        Shared with the async runtime: `fed.async_engine
        .trainer_fingerprint` is the single definition."""
        from repro.fed.async_engine import trainer_fingerprint
        return trainer_fingerprint(self.trainer)

    def _run_state(self) -> Dict[str, Any]:
        state: Dict[str, Any] = {
            "trainer": self.state,
            "round_idx": np.int64(self.round_idx),
            "sampler": self.sampler.state_dict(),
            "scheduler": {k: np.float64(v) for k, v in
                          self.scheduler.state_dict().items()},
            "personalize_tails": np.int64(int(self.personalize_tails)),
            "trainer_fingerprint": self._trainer_fingerprint(),
            "population": self.population.state_dict(),
        }
        meter = getattr(self.trainer, "meter", None)
        if meter is not None:
            state["meter"] = meter.state_dict()
        accountant = getattr(self.trainer, "accountant", None)
        if accountant is not None:
            # the zCDP ledger rides the checkpoint as float64 scalars —
            # npz round-trips them byte-identically, so the resumed run's
            # epsilon is exactly the uninterrupted run's
            state["privacy"] = accountant.state_dict()
        return state

    def save(self, ckpt_dir: str, *, keep_last: Optional[int] = 3) -> str:
        """Atomic full-run checkpoint; safe to call every round."""
        return save_checkpoint(ckpt_dir, self._run_state(),
                               step=self.round_idx, keep_last=keep_last)

    def restore(self, ckpt_dir: str) -> bool:
        """Resume from the newest checkpoint in `ckpt_dir`. Returns False
        (engine untouched) when the directory holds none."""
        run = load_latest(ckpt_dir)
        if run is None:
            return False
        trainer_state = jax.tree.map(jnp.asarray, run["trainer"])
        # round rides in the trainer state as int32; npz round-trips dtypes
        # exactly, so the restored pytree is bit-identical to the saved one
        self.state = trainer_state
        self.round_idx = int(run["round_idx"])
        self.sampler.load_state_dict(run["sampler"])
        if "scheduler" in run:
            self.scheduler.load_state_dict(run["scheduler"])
        if "personalize_tails" in run:
            saved = bool(int(run["personalize_tails"]))
            if saved != self.personalize_tails:
                raise ValueError(
                    f"personalize_tails mismatch on resume: checkpoint was "
                    f"written with {saved}, engine built with "
                    f"{self.personalize_tails} — the replayed rounds would "
                    f"silently diverge")
        if "trainer_fingerprint" in run:
            saved_fp = int(run["trainer_fingerprint"])
            if saved_fp != int(self._trainer_fingerprint()):
                raise ValueError(
                    "trainer mismatch on resume: the checkpoint was "
                    "written with different hyperparameters (protocol / "
                    "split / model config or wire codec) — rebuild the "
                    "trainer with the original flags")
        self.population.load_state_dict(run["population"])
        if self.personalize_tails and "params" in trainer_state:
            self.population.restore_tails(trainer_state["params"]["tail"])
        meter = getattr(self.trainer, "meter", None)
        if meter is not None and "meter" in run:
            meter.load_state_dict(_flatten_numeric(run["meter"]))
        accountant = getattr(self.trainer, "accountant", None)
        if accountant is not None:
            if "privacy" not in run:
                raise ValueError(
                    "DP trainer resumed from a checkpoint with no privacy "
                    "ledger — the pre-checkpoint releases would be "
                    "unaccounted; resume with the original DP flags")
            accountant.load_state_dict(run["privacy"])
        self.cohort_history = []
        return True


def _flatten_numeric(tree: Dict[str, Any], prefix: str = "") -> Dict[str, float]:
    """checkpoint.io round-trips nested dicts; the meter's state_dict is
    flat with '/'-keys — re-flatten what load produced."""
    out: Dict[str, float] = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flatten_numeric(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = float(np.asarray(v))
    return out
