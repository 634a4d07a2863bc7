"""Phase-1 local dataset pruning via EL2N (SFPrompt Sec. 3.2, Eq. (2)).

The client links W_h -> W_t (the body is skipped — no server traffic),
scores every local sample with the error-vector L2 norm, and keeps the
highest-scoring (1 - gamma) fraction. Only surviving samples ever produce
smashed-data traffic in phase 2.

NOTE: the paper's Algorithm 1 box writes the kept subset as
{z_i | i > gamma*n} after a *descending* sort, which would keep the LOWEST
scores — contradicting both the surrounding text ("retain the examples with
higher EL2N scores") and the EL2N literature. We follow the text: keep the
top (1-gamma) by score. (Recorded in EXPERIMENTS.md §Deviations.)
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import losses
from repro.core.split import SplitModel


def pruned_keep_count(n_local: int, prune_gamma: float,
                      batch_size: int) -> int:
    """How many of a client's `n_local` samples survive phase-1 pruning
    AND actually train in phase 2: the protocol keeps
    max(batch_size, n - floor(gamma * n)) rounded DOWN to a batch
    multiple (the phase-2 scan consumes full batches only). One shared
    definition for the protocol (`SFPromptTrainer._round`), the
    analytical cost model (`comm.measured_cost_inputs`), and the async
    runtime's flush weights — three copies of this rounding had already
    appeared and must never drift."""
    keep = max(batch_size, n_local - int(prune_gamma * n_local))
    return keep - keep % batch_size


def score_client_data(model: SplitModel, head_p, tail_p, prompt,
                      data: Dict[str, jnp.ndarray], *, batch_size: int,
                      impl: str = "ref") -> jnp.ndarray:
    """EL2N score for EVERY sample of one client's dataset (n, ...).
    Runs the LOCAL route (head -> tail), batched. When n is not a multiple
    of batch_size the final batch is padded by wrapping to the dataset's
    start and the padding's scores are masked off, so `prune_indices` ranks
    all n samples instead of silently never scoring the last
    n % batch_size of them."""
    n = jax.tree.leaves(data)[0].shape[0]
    nb = -(-n // batch_size)            # ceil: the last batch may be padded
    if nb * batch_size != n:
        # wrap-pad with real samples (scores of the padding are discarded
        # below); modular indexing also covers batch_size > n
        idx = jnp.arange(nb * batch_size) % n
        data = jax.tree.map(lambda x: jnp.take(x, idx, axis=0), data)
    batched = jax.tree.map(
        lambda x: x.reshape((nb, batch_size) + x.shape[1:]), data)

    def score_batch(_, batch):
        ho = model.head_fwd(head_p, prompt, batch, mode="train", impl=impl)
        to = model.tail_fwd(tail_p, ho["smashed"], ho)
        out = {"logits": to["logits"], "n_prefix": to.get("n_prefix", 0)}
        return None, losses.task_el2n(model.cfg, out, batch, impl=impl)

    _, scores = jax.lax.scan(score_batch, None, batched)
    return scores.reshape(-1)[:n]


def prune_indices(scores: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """Indices of the kept subset (static size): top (1-gamma) by EL2N."""
    n = scores.shape[0]
    keep = max(1, n - int(gamma * n))
    order = jnp.argsort(-scores)      # descending
    return order[:keep]


def prune_client_data(data: Dict[str, jnp.ndarray], scores: jnp.ndarray,
                      gamma: float) -> Tuple[Dict[str, jnp.ndarray], int]:
    idx = prune_indices(scores, gamma)
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=0), data), idx.shape[0]
