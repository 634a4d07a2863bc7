"""Public fused EL2N/CE op with impl dispatch.

Impls: "ref" (materialized oracle — builds the full (N, V) probability and
onehot tensors, the ground truth tests compare against), "fused" (one-pass
XLA form of the kernel identity — no onehot, no probability materialization,
the CPU surrogate of the Pallas kernel and the honest bench arm), "pallas" /
"interpret" (the TPU kernel). "auto" picks pallas on TPU, fused elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import row_blocks
from repro.kernels.el2n import ref
from repro.kernels.el2n.kernel import el2n_fwd


def _fused_scores(logits: jnp.ndarray, labels: jnp.ndarray):
    """One-pass identity (see ref.py's docstring): with m = max logit,
    Z = sum exp(l - m), S2 = sum exp(2(l - m)),
        ||p - y||^2 = S2/Z^2 - 2 exp(l_y - m)/Z + 1,  CE = m + log Z - l_y.
    Only (N,)-sized intermediates beyond exp(l - m) itself — no onehot and
    no (N, V) probability division. Clamped at 0 before the sqrt: near a
    perfectly-confident correct prediction the three terms cancel to
    rounding error, which must not go negative."""
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    ex = jnp.exp(logits - m[:, None])
    z = jnp.sum(ex, axis=-1)
    s2 = jnp.sum(ex * ex, axis=-1)
    ly = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    py = jnp.exp(ly - m) / z
    el2n = jnp.sqrt(jnp.maximum(s2 / (z * z) - 2.0 * py + 1.0, 0.0))
    ce = m + jnp.log(z) - ly
    return el2n, ce


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "block_v"))
def el2n_scores(logits: jnp.ndarray, labels: jnp.ndarray, *,
                impl: str = "auto", block_n: int = 256, block_v: int = 2048):
    """EL2N score + cross-entropy per row.

    logits: (N, V) float; labels: (N,) int32.
    Returns (el2n (N,), ce (N,)) in float32.
    """
    if impl in ("auto", "analysis"):
        impl = "pallas" if jax.default_backend() == "tpu" else "fused"
    if impl == "ref":
        return ref.el2n_scores(logits, labels)
    if impl == "fused":
        return _fused_scores(logits, labels)

    N, V = logits.shape
    bn, n_pad = row_blocks(N, block_n)
    bv = min(block_v, max(128, 1 << (V - 1).bit_length()))
    logits = jnp.pad(logits, ((0, n_pad - N), (0, (-V) % bv)))
    labels = jnp.pad(labels.astype(jnp.int32), (0, n_pad - N))
    el2n, ce = el2n_fwd(
        logits, labels[:, None], vocab=V,
        block_n=bn, block_v=bv, interpret=(impl == "interpret"))
    return el2n[:N, 0], ce[:N, 0]
