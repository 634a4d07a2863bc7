"""The SFPrompt three-phase protocol (Sec. 3, Fig. 3, Algorithms 1-2).

Per global round r:
  Phase 1 — client self-update: U local-loss epochs on (W_t, p) with the
            body skipped (zero server traffic), then EL2N dataset pruning.
  Phase 2 — split training over the pruned subset: head (client, frozen) ->
            body (server, frozen) -> tail (client, trainable); prompt grads
            flow back through the frozen body exactly as the paper's relayed
            backward signals — jax.grad through the chain is byte-identical
            mathematics.
  Phase 3 — sample-count-weighted FedAvg of (W_t, p).

Clients are FIRST-CLASS: every client-side tensor carries a leading client
axis K, all client math is vmapped over it (true per-client divergence), and
on a mesh that axis shards over ('pod','data') — see launch/dryrun.py.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import losses, pruning
from repro.core.aggregation import broadcast_to_clients, get_aggregator
from repro.core.local_update import dp_clip_and_noise, local_epochs
from repro.core.split import SplitModel
from repro.obs.trace import NOOP
from repro.optim import Optimizer, adamw, apply_updates, sgd
from repro.privacy.dp import DP_SEED, PrivacyAccountant
from repro.runtime.meter import EDGE, SECURE, TrafficMeter
from repro.sharding.rules import (cohort_pspecs, params_pspecs,
                                  report_fallbacks)

Params = Dict[str, Any]

WIRE_SEED = 23   # base PRNG stream for stochastic wire rounding


@dataclass(frozen=True)
class ProtocolConfig:
    clients_per_round: int = 5       # K
    local_epochs: int = 10           # U (phase 1)
    split_epochs: int = 1            # passes over pruned data (phase 2)
    batch_size: int = 16
    lr_local: float = 1e-2
    lr_split: float = 1e-2
    optimizer: str = "sgd"           # sgd | adamw
    momentum: float = 0.9
    impl: str = "ref"
    use_pruning: bool = True
    use_local_loss: bool = True      # False => the Fig-6 ablation arm
    return_client_trainable: bool = False
    # ^ also return each client's post-round (tail, prompt) BEFORE FedAvg —
    #   the fed engine stores these as personalized tails in the Population
    dp_clip: float = 0.0             # DP-SGD L2 clip on the client's round
    #   delta (0 disables the DP path entirely)
    dp_noise_multiplier: float = 0.0  # Gaussian noise as a multiple of the
    #   clip; > 0 activates the zCDP accountant
    dp_delta: float = 1e-5           # delta of the reported (eps, delta)


def make_optimizer(pcfg: ProtocolConfig, lr: float) -> Optimizer:
    if pcfg.optimizer == "adamw":
        return adamw(lr)
    return sgd(lr, momentum=pcfg.momentum)


class SFPromptTrainer:
    supports_partial = True   # round() accepts a participation dict

    def __init__(self, model: SplitModel, pcfg: ProtocolConfig,
                 aggregator=None, *, mesh=None, fsdp: bool = False,
                 donate_cohort: bool = False, tracer=None):
        self.model = model
        self.pcfg = pcfg
        # flight recorder (repro.obs): pure observation — the default NOOP
        # records nothing and the round math never reads it
        self.tracer = tracer if tracer is not None else NOOP
        self.opt_local = make_optimizer(pcfg, pcfg.lr_local)
        self.opt_split = make_optimizer(pcfg, pcfg.lr_split)
        # frozen segments enter the cohort vmap UNBATCHED (in_axes=None) so
        # no K copies of the body ever materialize — except for MoE, whose
        # ragged_dot vmap rule requires every operand batched at dim 0
        self._batch_frozen = getattr(model.cfg, "moe", None) is not None
        # mesh-sharded cohort dispatch: with a mesh, _round jits with
        # explicit in/out shardings — the K axis over the ('pod','data')
        # client plane, params replicated (or FSDP over 'data')
        self._mesh = mesh
        self._fsdp = fsdp
        self._donate_cohort = donate_cohort
        self._mesh_jit_cache: Dict[Any, Any] = {}
        # pluggable phase-3 aggregation: default is the clear path,
        # bit-identical to the seed's fedavg_partial; pass
        # aggregation.get_aggregator(secure=True) for masked secure agg
        self.aggregator = aggregator or get_aggregator()
        if pcfg.dp_noise_multiplier > 0 and pcfg.dp_clip <= 0:
            raise ValueError(
                "dp_noise_multiplier > 0 needs dp_clip > 0: the Gaussian "
                "noise is calibrated to the clip (sensitivity)")
        # zCDP ledger across rounds — only a noised mechanism has a
        # finite epsilon to account for
        self.accountant = (
            PrivacyAccountant(noise_multiplier=pcfg.dp_noise_multiplier,
                              l2_clip=pcfg.dp_clip, delta=pcfg.dp_delta)
            if pcfg.dp_noise_multiplier > 0 else None)
        self.meter = TrafficMeter()   # measured bytes across rounds
        self.meter.attach_tracer(self.tracer)
        self.last_client_trainable = None   # per-client (tail, prompt) of
        # the most recent round, populated iff pcfg.return_client_trainable
        self._round_jit = jax.jit(self._round) if mesh is None else None
        self._eval_jit = jax.jit(self._eval_batches)

    # ------------------------------------------------------- mesh dispatch
    def _frozen_arg(self, tree, k: int):
        """(operand, in_axes) for a frozen pytree entering the cohort vmap:
        unbatched with in_axes=None by default (HBM then scales with
        K * trainable, not K * model). MoE narrows the batched fallback to
        the ragged-dot EXPERT leaves only — jax.lax.ragged_dot has no vmap
        rule for an unbatched rhs, but attention/norm/router leaves vmap
        fine unbatched, so they stay in_axes=None and keep the no-K-copies
        HBM win outside the expert stacks."""
        if not self._batch_frozen:
            return tree, None

        def is_expert(path):
            return any(getattr(p, "key", None) == "experts" for p in path)

        operand = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.broadcast_to(x[None], (k,) + x.shape)
            if is_expert(p) else x, tree)
        axes = jax.tree_util.tree_map_with_path(
            lambda p, x: 0 if is_expert(p) else None, tree)
        return operand, axes

    def _sharding_tree(self, pspec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(self._mesh, s), pspec_tree,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def _build_mesh_jit(self, state, client_data, participation, init_tails):
        """jit of _round with explicit shardings over self._mesh: ONE
        dispatch trains the whole cohort, the K axis laid out on the
        client plane, frozen params replicated (FSDP over 'data' when
        enabled). donate_cohort=True additionally donates the state and
        the K-stacked cohort buffers — only safe when the caller (the
        FederatedEngine loop) never reuses them after the call."""
        mesh = self._mesh
        params = state["params"]
        k = jax.tree.leaves(client_data)[0].shape[0]
        state_sh = self._sharding_tree(
            {"params": params_pspecs(params, mesh, fsdp=self._fsdp),
             "round": PartitionSpec()})
        data_sh = self._sharding_tree(cohort_pspecs(client_data, mesh))
        part_sh = self._sharding_tree(cohort_pspecs(participation, mesh))
        tails_sh = (None if init_tails is None else
                    self._sharding_tree(cohort_pspecs(init_tails, mesh)))
        repl = NamedSharding(mesh, PartitionSpec())
        extras_sh: Any = {}
        if self.pcfg.return_client_trainable:
            proto = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((k,) + x.shape, x.dtype),
                {"tail": params["tail"], "prompt": params["prompt"]})
            extras_sh = {"trainable": self._sharding_tree(
                cohort_pspecs(proto, mesh))}
        # surface any divisibility fallbacks the spec builders recorded —
        # a rule that wanted 'model'/'data' but could not divide it means
        # this mesh silently replicates something it was sized to shard
        report_fallbacks("protocol.mesh_jit", self.tracer)
        donate = (0, 1, 3) if self._donate_cohort else ()
        return jax.jit(
            self._round,
            in_shardings=(state_sh, data_sh, part_sh, tails_sh),
            out_shardings=(state_sh, repl, extras_sh),
            donate_argnums=donate)

    def _get_round_jit(self, state, client_data, participation, init_tails):
        if self._mesh is None:
            return self._round_jit
        k = jax.tree.leaves(client_data)[0].shape[0]
        key = (k, init_tails is None)
        if key not in self._mesh_jit_cache:
            self._mesh_jit_cache[key] = self._build_mesh_jit(
                state, client_data, participation, init_tails)
        return self._mesh_jit_cache[key]

    # ------------------------------------------------------------- state
    def init(self, key) -> Params:
        return {"params": self.model.init(key),
                "round": jnp.zeros((), jnp.int32)}

    def phase2_keep(self, n_local: int) -> int:
        """Samples per client that actually train phase 2 — `n_local`
        shrunk by EL2N pruning when it is active. Static per shape, so
        the async engine can weight buffered contributions with exactly
        the factor `_round` folds into its FedAvg weights."""
        if self.pcfg.use_pruning and self.model.split.prune_gamma > 0:
            return pruning.pruned_keep_count(
                n_local, self.model.split.prune_gamma, self.pcfg.batch_size)
        return n_local

    # ------------------------------------------------------------- phase 2
    def _split_loss(self, params_frozen, trainable, batch, wire_key):
        """Phase-2 loss with the head->body and body->tail hops crossing the
        real wire: codec'd forward activations, codec'd backward gradients
        (via the boundary custom-VJP), measured bytes in the aux."""
        model, pcfg = self.model, self.pcfg
        k_hb, k_bt = jax.random.split(wire_key)
        ho = model.head_fwd(params_frozen["head"], trainable["prompt"], batch,
                            mode="train", impl=pcfg.impl)
        x_hb, b_hb = model.wire.head_body.transmit(
            ho["smashed"], key=k_hb, train=True)
        bo = model.body_fwd(params_frozen["body"], x_hb, ho)
        x_bt, b_bt = model.wire.body_tail.transmit(
            bo["smashed"], key=k_bt, train=True)
        to = model.tail_fwd(trainable["tail"], x_bt, ho)
        out = {"logits": to["logits"], "n_prefix": to.get("n_prefix", 0),
               "aux": ho["aux"] + bo["aux"] + to["aux"]}
        loss, _ = losses.task_loss(model.cfg, out, batch, impl=pcfg.impl)
        return loss, {"wire": {"head_body": b_hb, "body_tail": b_bt}}

    def _split_epochs(self, frozen, trainable, opt_state, data, wire_key):
        pcfg = self.pcfg
        n = jax.tree.leaves(data)[0].shape[0]
        nb = max(1, n // pcfg.batch_size)
        batched = jax.tree.map(
            lambda x: x[: nb * pcfg.batch_size].reshape(
                (nb, pcfg.batch_size) + x.shape[1:]), data)
        grad_fn = jax.value_and_grad(
            lambda tr, b, k: self._split_loss(frozen, tr, b, k),
            has_aux=True)

        def one_batch(carry, batch):
            tr, os, acc, wire, step = carry
            (loss, aux), grads = grad_fn(
                tr, batch, jax.random.fold_in(wire_key, step))
            updates, os = self.opt_split.update(grads, os, tr)
            tr = apply_updates(tr, updates)
            wire = jax.tree.map(jnp.add, wire, aux["wire"])
            return (tr, os, acc + loss, wire, step + 1), None

        def one_epoch(carry, _):
            carry, _ = jax.lax.scan(one_batch, carry, batched)
            return carry, None

        wire0 = {"head_body": jnp.float32(0.0),
                 "body_tail": jnp.float32(0.0)}
        (trainable, opt_state, acc, wire, _), _ = jax.lax.scan(
            one_epoch,
            (trainable, opt_state, jnp.float32(0.0), wire0, jnp.int32(0)),
            None, length=pcfg.split_epochs)
        return trainable, opt_state, acc / (pcfg.split_epochs * nb), wire

    # ------------------------------------------------------------- round
    def _round(self, state: Params, client_data, participation,
               init_tails) -> Tuple[Params, Dict, Dict]:
        """client_data: pytree with leading (K, n_local, ...) axes — the
        SAMPLED COHORT gathered from a (possibly huge) population, not the
        population itself.

        participation: {"transmit": (K,), "aggregate": (K,)} from a
        `fed.RoundPlan` — transmit scales each client's measured wire bytes
        (a straggler cut off mid-round only sent part of its traffic),
        aggregate weights phase-3 FedAvg (0 drops the client). All-ones
        reproduces the seed repo's synchronous full-participation round
        byte-for-byte.

        init_tails: optional K-stacked tail pytree — each client starts
        phase 1 from its OWN tail (the fed engine's personalized-tail
        regime) instead of the broadcast global tail; None broadcasts."""
        model, pcfg = self.model, self.pcfg
        params = state["params"]
        K = jax.tree.leaves(client_data)[0].shape[0]
        n_local = jax.tree.leaves(client_data)[0].shape[1]

        trainable = broadcast_to_clients(
            {"tail": params["tail"], "prompt": params["prompt"]}, K)
        if init_tails is not None:
            trainable = dict(trainable, tail=init_tails)
        metrics: Dict[str, Any] = {}

        # ---- Phase 1a: local-loss self-update (vmap over clients; the
        # frozen head rides UNBATCHED through in_axes=None — no K copies)
        with jax.named_scope("sfprompt.phase1_local"):
            if pcfg.use_local_loss and pcfg.local_epochs > 0:
                opt_state = jax.vmap(self.opt_local.init)(trainable)
                head_arg, head_ax = self._frozen_arg(params["head"], K)

                def one_client(hd, tr, os, d):
                    return local_epochs(
                        model, hd, tr, self.opt_local, os, d,
                        batch_size=pcfg.batch_size, n_epochs=pcfg.local_epochs,
                        impl=pcfg.impl)

                trainable, opt_state, local_loss = jax.vmap(
                    one_client, in_axes=(head_ax, 0, 0, 0))(
                    head_arg, trainable, opt_state, client_data)
                metrics["local_loss"] = local_loss.mean()

        # ---- Phase 1b: EL2N pruning (vmap over clients)
        with jax.named_scope("sfprompt.phase1b_prune"):
            if pcfg.use_pruning and model.split.prune_gamma > 0:
                head_arg, head_ax = self._frozen_arg(params["head"], K)

                def score_one(hd, tr, d):
                    return pruning.score_client_data(
                        model, hd, tr["tail"], tr["prompt"], d,
                        batch_size=pcfg.batch_size, impl=pcfg.impl)

                scores = jax.vmap(score_one, in_axes=(head_ax, 0, 0))(
                    head_arg, trainable, client_data)
                keep = self.phase2_keep(n_local)
                order = jnp.argsort(-scores, axis=1)[:, :keep]
                pruned = jax.tree.map(
                    lambda x: jnp.take_along_axis(
                        x, order.reshape((K, keep) + (1,) * (x.ndim - 2)),
                        axis=1) if x.ndim > 2 else
                    jnp.take_along_axis(x, order, axis=1),
                    client_data)
                metrics["el2n_mean"] = scores.mean()
                metrics["kept_frac"] = keep / n_local
            else:
                pruned, keep = client_data, n_local

        # ---- Phase 2: split training (vmap over clients; the frozen
        # {head, body} enter unbatched — phase-2 peak HBM scales with
        # K * (tail + prompt + opt state), not K * body — batched only on
        # the MoE path whose ragged_dot vmaps solely at dim 0)
        with jax.named_scope("sfprompt.phase2_split"):
            opt_state = jax.vmap(self.opt_split.init)(trainable)
            frozen_arg, frozen_ax = self._frozen_arg(
                {"head": params["head"], "body": params["body"]}, K)
            wire_keys = jax.random.split(
                jax.random.fold_in(jax.random.PRNGKey(WIRE_SEED),
                                   state["round"]), K)

            def split_one(fz, tr, os, d, wk):
                return self._split_epochs(fz, tr, os, d, wk)

            trainable, opt_state, split_loss, wire = jax.vmap(
                split_one, in_axes=(frozen_ax, 0, 0, 0, 0))(
                frozen_arg, trainable, opt_state, pruned, wire_keys)
            metrics["split_loss"] = split_loss.mean()
            transmit = participation["transmit"].astype(jnp.float32)
            for name, per_client in wire.items():
                # a straggler that died / hit the deadline only sent a
                # fraction of its phase-2 traffic — scale the measured
                # per-client bytes
                metrics[f"wire/{name}_bytes"] = (per_client * transmit).sum()

        # the DP clip below rides inside phase 3's scope: it is the last
        # step before the upload
        with jax.named_scope("sfprompt.phase3_aggregate"):
            # ---- DP-SGD on the client update: clip the round delta
            # against the broadcast globals, add calibrated Gaussian noise
            # — BEFORE the server (or the masked aggregator) ever sees the
            # upload
            if pcfg.dp_clip > 0:
                # the reference is pure tree arithmetic (no model ops), so it
                # rides unbatched on every architecture
                reference = {"tail": params["tail"],
                             "prompt": params["prompt"]}
                dp_keys = jax.random.split(
                    jax.random.fold_in(jax.random.PRNGKey(DP_SEED),
                                       state["round"]), K)

                def dp_one(tr, ref, dk):
                    return dp_clip_and_noise(
                        tr, ref, dk, l2_clip=pcfg.dp_clip,
                        noise_multiplier=pcfg.dp_noise_multiplier)

                trainable, dp_norm = jax.vmap(dp_one, in_axes=(0, None, 0))(
                    trainable, reference, dp_keys)
                metrics["dp/delta_norm"] = dp_norm.mean()

            # ---- Phase 3: participation-corrected weighted FedAvg of
            # (tail, prompt) through the pluggable aggregator; dropped clients
            # are excluded, a fully-lost round falls back to the pre-round
            # globals. The secure path uploads masked uint32 ring tensors the
            # server cannot invert (see repro/privacy/secure_agg.py).
            aggregate = participation["aggregate"].astype(jnp.float32)
            weights = jnp.float32(keep) * aggregate
            fallback = {"tail": params["tail"], "prompt": params["prompt"]}
            agg, agg_wire = self.aggregator.aggregate(trainable, weights,
                                                      fallback, state["round"])
            new_params = dict(params)
            new_params["tail"] = agg["tail"]
            new_params["prompt"] = agg["prompt"]
            n_up = (aggregate > 0).sum()
            param_bytes = jnp.float32(sum(
                x.size * x.dtype.itemsize
                for x in jax.tree.leaves(fallback)))
            if agg_wire:
                # metered aggregator: fp32 broadcast down to all K; the uplink
                # is whatever the aggregator metered (masked ring uploads on
                # the secure path) or the clear survivors-only default; key
                # agreement / escrow reveals and the hierarchical edge->global
                # backhaul ride their own streams
                up = agg_wire.get("params_up", n_up * param_bytes)
                metrics["wire/params_bytes"] = K * param_bytes + up
                if SECURE in agg_wire:
                    metrics[f"wire/{SECURE}_bytes"] = agg_wire[SECURE]
                if EDGE in agg_wire:
                    metrics[f"wire/{EDGE}_bytes"] = agg_wire[EDGE]
            else:
                # clear path: (tail, prompt) travel server->client for all K at
                # round start and client->server only for the survivors
                metrics["wire/params_bytes"] = (K + n_up) * param_bytes
            metrics["cohort/active"] = n_up
            metrics["cohort/transmit_sum"] = transmit.sum()

        extras = ({"trainable": trainable}
                  if pcfg.return_client_trainable else {})
        return ({"params": new_params, "round": state["round"] + 1},
                metrics, extras)

    def round(self, state: Params, client_data,
              participation: Optional[Dict[str, Any]] = None,
              init_tails=None) -> Tuple[Params, Dict]:
        """Run one global round on a sampled cohort. `participation` is a
        `fed.RoundPlan.participation()` dict; None means every client is on
        time (the seed behavior). `init_tails` (K-stacked) starts each
        client from its own personalized tail."""
        K = jax.tree.leaves(client_data)[0].shape[0]
        if participation is None:
            ones = jnp.ones((K,), jnp.float32)
            participation = {"transmit": ones, "aggregate": ones}
        tracer = self.tracer
        with tracer.span("sfprompt.round") as sp:
            with tracer.span("sfprompt.dispatch"):
                round_jit = self._get_round_jit(state, client_data,
                                                participation, init_tails)
                if tracer.enabled:
                    tracer.event("round.dispatch", level=2, cohort=K,
                                 personalized_tails=init_tails is not None)
                state, metrics, extras = round_jit(state, client_data,
                                                   participation, init_tails)
                self.last_client_trainable = extras.get("trainable")
            with tracer.span("sfprompt.sync"):
                # the host waits here for the device to finish the round
                metrics = {k: float(v) for k, v in metrics.items()}
            if self.accountant is not None:
                # one Gaussian release of each sampled client's update per
                # round — the ledger tracks the per-client (local-model)
                # view
                self.accountant.spend()
                metrics["dp/epsilon"] = self.accountant.epsilon()
            wire = {k.removeprefix("wire/").removesuffix("_bytes"): v
                    for k, v in metrics.items() if k.startswith("wire/")}
            self.meter.absorb(wire, clients=metrics.get("cohort/active"))
            if tracer.enabled:
                # the span carries the SAME floats the meter absorbed —
                # per-span byte attrs sum exactly to the stream totals
                sp.set(round=self.meter.rounds, cohort=K,
                       active=metrics.get("cohort/active"), **wire)
        return state, metrics

    def client_updates(self, state: Params, client_data,
                       transmit=None) -> Tuple[Any, Dict]:
        """Phases 1-2 (+ the per-client DP step) for a dispatched cohort
        WITHOUT phase-3 aggregation — the async runtime's dispatch
        primitive. Returns (K-stacked (tail, prompt) contributions,
        round metrics); the global params are untouched.

        Implemented as the ordinary jitted round with an all-zero
        `aggregate` vector: `fedavg_partial` then returns the pre-round
        globals bit-exactly, so the SAME compiled round serves both the
        synchronous barrier and async dispatch (the bit-identity the
        async tests pin depends on this — no second lowering of phase 2
        exists to drift). The metered `params` stream carries only the
        K-client downlink; uploads are billed when each delta reaches
        the server's buffer. `transmit` (K,) scales phase-2 wire bytes
        for clients that die mid-flight (fraction sent before death).
        Requires ProtocolConfig(return_client_trainable=True)."""
        if not self.pcfg.return_client_trainable:
            raise ValueError(
                "client_updates needs ProtocolConfig("
                "return_client_trainable=True) — without it the jitted "
                "round aggregates and discards the per-client trees")
        K = jax.tree.leaves(client_data)[0].shape[0]
        if transmit is None:
            transmit = jnp.ones((K,), jnp.float32)
        participation = {"transmit": jnp.asarray(transmit, jnp.float32),
                         "aggregate": jnp.zeros((K,), jnp.float32)}
        _, metrics = self.round(state, client_data, participation)
        return self.last_client_trainable, metrics

    # ------------------------------------------------------------- eval
    def _eval_batches(self, params, batched):
        def one(carry, batch):
            out = self.model.forward(params, batch, route="split",
                                     mode="train", impl=self.pcfg.impl)
            loss, m = losses.task_loss(self.model.cfg, out, batch,
                                       impl=self.pcfg.impl)
            return carry, (m["ce"], m["acc"])

        _, (ce, acc) = jax.lax.scan(one, None, batched)
        return ce.mean(), acc.mean()

    def evaluate(self, params: Params, data, *, batch_size: int = 32) -> Dict:
        n = jax.tree.leaves(data)[0].shape[0]
        nb = max(1, n // batch_size)
        batched = jax.tree.map(
            lambda x: x[: nb * batch_size].reshape(
                (nb, batch_size) + x.shape[1:]), data)
        ce, acc = self._eval_jit(params, batched)
        return {"ce": float(ce), "acc": float(acc)}
