"""jit-able production steps for the dry-run and the real launcher.

train_step  — SFPrompt steady state: one phase-2 split minibatch per client
              (vmapped over the client axis, microbatch gradient
              accumulation, frozen head/body, grads only for (tail, prompt))
              followed by the phase-3 FedAvg collective.
serve_step  — split-inference prefill / decode against the KV cache.

Loss modes:
  'logits' — paper-faithful: materialize logits, CE on top (baseline).
  'fused'  — beyond-paper: hidden @ W_head folded into the fused EL2N/CE
             computation per vocab shard (no (B,S,V) f32 tensor).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import losses
from repro.core.aggregation import broadcast_to_clients, fedavg
from repro.models import layers as L
from repro.core.split import SplitModel
from repro.kernels.el2n.ops import el2n_scores
from repro.optim import apply_updates, sgd

ACT_DTYPE = jnp.bfloat16


def _fused_lm_loss(hidden, head_w, tokens, n_prefix, softcap=None):
    """CE without materializing (B, S, V) f32 logits: contract per-position
    in bf16, reduce stats in f32 via the fused EL2N/CE identity."""
    lg = (hidden[:, n_prefix:-1, :] @ head_w.astype(hidden.dtype))
    if softcap:
        lg = softcap * jnp.tanh(lg / softcap)
    V = lg.shape[-1]
    _, ce = el2n_scores(lg.reshape(-1, V).astype(jnp.float32),
                        tokens[:, 1:].reshape(-1))
    return ce.mean()


def make_split_loss(model: SplitModel, *, impl="ref", remat=True,
                    loss_mode="logits", unroll=False):
    """Phase-2 loss with both cut points crossing the model's wire
    boundaries (codec'd activations forward, codec'd gradients backward).
    `wire_key=None` uses deterministic round-to-nearest for stochastic
    codecs — pass a key for unbiased stochastic rounding."""
    cfg = model.cfg

    def split_loss(trainable, frozen, batch, wire_key=None):
        k_hb = k_bt = None
        if wire_key is not None:
            k_hb, k_bt = jax.random.split(wire_key)
        ho = model.head_fwd(frozen["head"], trainable["prompt"], batch,
                            mode="train", impl=impl, dtype=ACT_DTYPE,
                            remat=remat, unroll=unroll)
        x_hb, _ = model.wire.head_body.transmit(ho["smashed"], key=k_hb)
        bo = model.body_fwd(frozen["body"], x_hb, ho)
        x_bt, _ = model.wire.body_tail.transmit(bo["smashed"], key=k_bt)
        if loss_mode == "fused" and not cfg.num_classes:
            x, aux_t, _ = model._seg_fwd(
                trainable["tail"], "tail", model.split.tail_cycles,
                x_bt, model._ctx_from(ho), None)
            hidden = L.apply_norm(trainable["tail"]["final_norm"], x, cfg.norm)
            loss = _fused_lm_loss(hidden, trainable["tail"]["head"]["w"],
                                  batch["tokens"], ho["n_prefix"],
                                  cfg.final_logit_softcap)
            return loss + ho["aux"] + bo["aux"] + aux_t
        to = model.tail_fwd(trainable["tail"], x_bt, ho)
        out = {"logits": to["logits"].astype(jnp.float32),
               "n_prefix": to.get("n_prefix", 0),
               "aux": ho["aux"] + bo["aux"] + to["aux"]}
        loss, _ = losses.task_loss(cfg, out, batch, impl=impl)
        return loss

    return split_loss


def make_train_step(model: SplitModel, *, n_clients: int,
                    microbatches: int = 1, lr: float = 1e-2,
                    impl: str = "ref", loss_mode: str = "logits",
                    remat: bool = True, unroll: bool = False):
    """Returns (train_step, opt). train_step(frozen, trainable_k,
    opt_state_k, batch_k) -> (trainable_k, opt_state_k, loss)."""
    opt = sgd(lr, momentum=0.9)
    split_loss = make_split_loss(model, impl=impl, remat=remat,
                                 loss_mode=loss_mode, unroll=unroll)

    def per_client(frozen, trainable, opt_state, batch):
        b = jax.tree.leaves(batch)[0].shape[0]
        mb = b // microbatches

        mbs = jax.tree.map(
            lambda x: x.reshape((microbatches, mb) + x.shape[1:]), batch)
        grad_fn = jax.value_and_grad(
            lambda tr, bch: split_loss(tr, frozen, bch))

        def one_mb(carry, mbatch):
            loss_acc, g_acc = carry
            loss, g = grad_fn(trainable, mbatch)
            return (loss_acc + loss,
                    jax.tree.map(jnp.add, g_acc, g)), None

        zero_g = jax.tree.map(jnp.zeros_like, trainable)
        (loss, grads), _ = jax.lax.scan(one_mb, (jnp.float32(0.0), zero_g), mbs)
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        updates, opt_state = opt.update(grads, opt_state, trainable)
        trainable = apply_updates(trainable, updates)
        return trainable, opt_state, loss / microbatches

    def train_step(frozen, trainable_k, opt_state_k, batch_k):
        # broadcast frozen segments over the client axis: ragged_dot (MoE)
        # vmaps only with all operands batched at dim 0; XLA keeps the
        # broadcast unmaterialized per shard.
        frozen_k = broadcast_to_clients(frozen, n_clients)
        trainable_k, opt_state_k, loss_k = jax.vmap(per_client)(
            frozen_k, trainable_k, opt_state_k, batch_k)
        # Phase-3 aggregation: the protocol's signature collective
        agg = fedavg(trainable_k, jnp.ones((n_clients,), jnp.float32))
        trainable_k = broadcast_to_clients(agg, n_clients)
        return trainable_k, opt_state_k, loss_k.mean()

    return train_step, opt


def make_prefill_step(model: SplitModel, *, impl: str = "ref",
                      unroll: bool = False, with_wire_bytes: bool = False,
                      dtype=ACT_DTYPE, donate_cache: bool = False):
    """Prefill crosses both wire boundaries once (forward only); with
    `with_wire_bytes` the step also returns the measured per-link bytes.
    `dtype` is the activation dtype (bf16 production default; the serving
    engine's logit-equivalence tests run fp32). `donate_cache` returns the
    step pre-jitted with the cache argument DONATED — the caller must
    replace its cache with the returned one and never touch the old pytree
    (the serving/decode loops already do); in exchange the KV cache updates
    in place instead of being copied every step."""
    def prefill_step(params, batch, cache):
        out = model.forward(params, batch, route="split", mode="prefill",
                            cache=cache, impl=impl, dtype=dtype,
                            unroll=unroll)
        if with_wire_bytes:
            return out["logits"][:, -1, :], out["cache"], out["wire_bytes"]
        return out["logits"][:, -1, :], out["cache"]
    if donate_cache:
        return jax.jit(prefill_step, donate_argnums=(2,))
    return prefill_step


def make_decode_step(model: SplitModel, *, impl: str = "ref",
                     unroll: bool = False, with_wire_bytes: bool = False,
                     dtype=ACT_DTYPE, donate_cache: bool = False):
    """One greedy decode token against the KV cache; `donate_cache` as in
    `make_prefill_step` (the cache pytree is donated and updated in
    place — the decode hot loop's biggest per-step copy)."""
    def decode_step(params, batch, cache):
        out = model.forward(params, batch, route="split", mode="decode",
                            cache=cache, impl=impl, dtype=dtype,
                            unroll=unroll)
        logits = out["logits"][:, 0, :]
        next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
        if with_wire_bytes:
            return next_tok, logits, out["cache"], out["wire_bytes"]
        return next_tok, logits, out["cache"]
    if donate_cache:
        return jax.jit(decode_step, donate_argnums=(2,))
    return decode_step
