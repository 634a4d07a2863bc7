"""Fused EL2N + CE as a Pallas TPU kernel.

The pruning phase of SFPrompt scores every local sample with
||softmax(logits) - onehot||_2. For LM-sized vocabularies (32k..256k) the
naive path materializes an (N, V) probability tensor in HBM. This kernel
streams vocab tiles through VMEM once, maintaining per-row online-softmax
statistics (m, Z, S2 = sum exp(2(l-m)), l_y) in scratch, and emits the score
and CE without ever writing probabilities:

    ||p - y||^2 = S2/Z^2 - 2 exp(l_y - m)/Z + 1
    CE          = m + log Z - l_y

Tiling: grid = (N/block_n, V/block_v); vocab is the inner sequential axis.
Arithmetic intensity: one pass over logits, O(N) outputs — purely
bandwidth-bound, so the win vs the ref path is the removed (N, V) probs
round-trip plus the removed second max/sum pass (~3x HBM traffic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
NEG = -2.0 ** 30


def _el2n_kernel(logits_ref, labels_ref, el2n_ref, ce_ref,
                 m_scr, z_scr, s2_scr, ly_scr, *,
                 block_n: int, block_v: int, n_v_blocks: int, vocab: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        z_scr[...] = jnp.zeros_like(z_scr)
        s2_scr[...] = jnp.zeros_like(s2_scr)
        ly_scr[...] = jnp.full_like(ly_scr, NEG)

    l = logits_ref[...].astype(jnp.float32)            # (block_n, block_v)
    cols = iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    valid = cols < vocab
    l = jnp.where(valid, l, NEG)

    labels = labels_ref[...]                            # (block_n, 1) int32
    hit = cols == labels                                # (block_n, block_v)
    ly_tile = jnp.max(jnp.where(hit, l, NEG), axis=-1, keepdims=True)
    ly_scr[...] = jnp.maximum(ly_scr[...], jnp.broadcast_to(ly_tile, ly_scr.shape))

    m_prev = m_scr[:, :1]
    m_cur = jnp.max(l, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    a1 = jnp.exp(m_prev - m_new)                        # rescale for Z
    a2 = jnp.exp(2.0 * (m_prev - m_new))                # rescale for S2
    e = jnp.where(valid, jnp.exp(l - m_new), 0.0)
    z_new = a1 * z_scr[:, :1] + jnp.sum(e, axis=-1, keepdims=True)
    s2_new = a2 * s2_scr[:, :1] + jnp.sum(e * e, axis=-1, keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    z_scr[...] = jnp.broadcast_to(z_new, z_scr.shape)
    s2_scr[...] = jnp.broadcast_to(s2_new, s2_scr.shape)

    @pl.when(iv == n_v_blocks - 1)
    def _finalize():
        m = m_scr[:, :1]
        z = jnp.maximum(z_scr[:, :1], 1e-30)
        s2 = s2_scr[:, :1]
        ly = ly_scr[:, :1]
        py = jnp.exp(ly - m) / z
        sq = jnp.maximum(s2 / (z * z) - 2.0 * py + 1.0, 0.0)
        el2n_ref[...] = jnp.broadcast_to(jnp.sqrt(sq), el2n_ref.shape)
        ce_ref[...] = jnp.broadcast_to(m + jnp.log(z) - ly, ce_ref.shape)


def el2n_fwd(logits: jnp.ndarray, labels: jnp.ndarray, *,
             vocab: int, block_n: int = 256, block_v: int = 2048,
             interpret: bool = False):
    """logits (N, Vp), labels (N, 1) int32; N % block_n == Vp % block_v == 0.
    Returns (el2n (N, 1), ce (N, 1)) — column 0 of LANES-wide outputs."""
    N, Vp = logits.shape
    nv = Vp // block_v
    kernel = functools.partial(
        _el2n_kernel, block_n=block_n, block_v=block_v, n_v_blocks=nv,
        vocab=vocab)
    el2n, ce = pl.pallas_call(
        kernel,
        grid=(N // block_n, nv),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, LANES), jnp.float32),
            jax.ShapeDtypeStruct((N, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, LANES), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sfprompt_el2n",
    )(logits, labels)
    return el2n[:, :1], ce[:, :1]
