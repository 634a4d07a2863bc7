"""Task losses + EL2N scoring glue.

For LM-style archs the loss region excludes the prompt/patch prefix
(``n_prefix``); EL2N for a sequence is the mean over next-token positions of
||softmax(logits) - onehot||_2 (the classifier Eq. (2) applied per position —
DESIGN.md §Arch-applicability). The fused el2n kernel computes both the CE
and the EL2N statistics in one pass over the vocab.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp

from repro.kernels.el2n.ops import el2n_scores


def lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray, n_prefix: int,
            *, impl: str = "auto") -> Tuple[jnp.ndarray, Dict]:
    """Next-token CE on the text region. logits (B, T, V); tokens (B, S)."""
    B, T, V = logits.shape
    lg = logits[:, n_prefix:-1, :]                    # predicts tokens[1:]
    tg = tokens[:, 1:]
    # differentiated -> ref path (the fused kernel is for scoring; its
    # custom-VJP variant is a perf-pass item)
    _, ce = el2n_scores(lg.reshape(-1, V), tg.reshape(-1), impl="ref")
    loss = ce.mean()
    acc = jnp.mean((jnp.argmax(lg, -1) == tg).astype(jnp.float32))
    return loss, {"ce": loss, "acc": acc}


def lm_el2n(logits: jnp.ndarray, tokens: jnp.ndarray, n_prefix: int,
            *, impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-sequence EL2N score + CE. Returns (el2n (B,), ce (B,))."""
    B, T, V = logits.shape
    lg = logits[:, n_prefix:-1, :]
    tg = tokens[:, 1:]
    n = tg.shape[1]
    el2n, ce = el2n_scores(lg.reshape(-1, V), tg.reshape(-1), impl=impl)
    return el2n.reshape(B, n).mean(-1), ce.reshape(B, n).mean(-1)


def classifier_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                    *, impl: str = "auto") -> Tuple[jnp.ndarray, Dict]:
    """logits (B, C), integer labels (B,)."""
    _, ce = el2n_scores(logits, labels, impl="ref")
    loss = ce.mean()
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"ce": loss, "acc": acc}


def classifier_el2n(logits: jnp.ndarray, labels: jnp.ndarray,
                    *, impl: str = "auto"):
    """SFPrompt Eq. (2) exactly: per-sample ||softmax - onehot||_2."""
    el2n, ce = el2n_scores(logits, labels, impl=impl)
    return el2n, ce


def task_loss(cfg, out: Dict, batch: Dict, *, impl: str = "auto"):
    """Dispatch on arch type; adds the MoE aux loss."""
    if cfg.num_classes:
        loss, metrics = classifier_loss(out["logits"], batch["labels"],
                                        impl=impl)
    else:
        loss, metrics = lm_loss(out["logits"], batch["tokens"],
                                out.get("n_prefix", 0), impl=impl)
    loss = loss + out.get("aux", 0.0)
    metrics["loss"] = loss
    return loss, metrics


def task_el2n(cfg, out: Dict, batch: Dict, *, impl: str = "auto"):
    if cfg.num_classes:
        return classifier_el2n(out["logits"], batch["labels"], impl=impl)[0]
    return lm_el2n(out["logits"], batch["tokens"], out.get("n_prefix", 0),
                   impl=impl)[0]
