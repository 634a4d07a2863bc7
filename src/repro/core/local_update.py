"""Phase-1 client self-update: local-loss updates (SFPrompt Sec. 3.2, Eq. (1)).

The client connects W_h directly to W_t (body skipped), and runs U local
epochs updating only (W_t, prompt) — the head stays frozen. This phase costs
ZERO server communication; it substitutes for the per-epoch smashed-data
round trips that make naive SFL expensive.

All functions operate on ONE client and are vmapped over the client axis by
the protocol (head params broadcast, tail/prompt/opt-state per-client).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import losses
from repro.core.split import SplitModel
from repro.optim import Optimizer, apply_updates


def local_loss_fn(model: SplitModel, head_p, trainable, batch, *,
                  impl: str = "ref"):
    """L_C(x; (W_h, W_t); p): loss of the head->tail local model."""
    tail_p, prompt = trainable["tail"], trainable["prompt"]
    ho = model.head_fwd(head_p, prompt, batch, mode="train", impl=impl)
    to = model.tail_fwd(tail_p, ho["smashed"], ho)
    out = {"logits": to["logits"], "n_prefix": to.get("n_prefix", 0),
           "aux": ho["aux"] + to["aux"]}
    return losses.task_loss(model.cfg, out, batch, impl=impl)


def local_epochs(model: SplitModel, head_p, trainable, opt: Optimizer,
                 opt_state, data: Dict[str, jnp.ndarray], *,
                 batch_size: int, n_epochs: int, impl: str = "ref"):
    """U epochs of local-loss SGD over one client's full dataset.
    Returns (trainable, opt_state, mean_loss)."""
    n = jax.tree.leaves(data)[0].shape[0]
    nb = max(1, n // batch_size)
    batched = jax.tree.map(
        lambda x: x[: nb * batch_size].reshape((nb, batch_size) + x.shape[1:]),
        data)
    grad_fn = jax.grad(
        lambda tr, b: local_loss_fn(model, head_p, tr, b, impl=impl)[0])

    def one_batch(carry, batch):
        trainable, opt_state, acc = carry
        loss, _ = local_loss_fn(model, head_p, trainable, batch, impl=impl)
        grads = grad_fn(trainable, batch)
        updates, opt_state = opt.update(grads, opt_state, trainable)
        trainable = apply_updates(trainable, updates)
        return (trainable, opt_state, acc + loss), None

    def one_epoch(carry, _):
        carry, _ = jax.lax.scan(one_batch, carry, batched)
        return carry, None

    (trainable, opt_state, acc), _ = jax.lax.scan(
        one_epoch, (trainable, opt_state, jnp.float32(0.0)),
        None, length=n_epochs)
    return trainable, opt_state, acc / (n_epochs * nb)


def dp_clip_and_noise(trainable, reference, key, *, l2_clip: float,
                      noise_multiplier: float):
    """DP-SGD on ONE client's round update (vmapped over clients by the
    protocol, like everything above).

    The privatized quantity is the client's DELTA against the broadcast
    pre-round globals — clipping absolute params would destroy them, and
    the delta is what the server aggregates. Per DP-SGD: scale the delta to
    global L2 norm <= l2_clip, add N(0, (noise_multiplier * l2_clip)^2)
    per coordinate, and rebuild the params the client uploads. Returns
    (privatized trainable, pre-clip delta norm for diagnostics)."""
    # lazy like aggregation.get_aggregator: the core layer only touches
    # the privacy subsystem when the DP path is actually taken
    from repro.privacy.dp import clip_tree, gaussian_noise_tree
    delta = jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        trainable, reference)
    delta, norm = clip_tree(delta, l2_clip)
    noise = gaussian_noise_tree(key, delta, noise_multiplier * l2_clip)
    return jax.tree.map(
        lambda ref, d, n: (ref.astype(jnp.float32) + d + n)
        .astype(ref.dtype), reference, delta, noise), norm
