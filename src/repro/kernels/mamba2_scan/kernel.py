"""Mamba-2 SSD as a chunked-matmul Pallas TPU kernel.

This is the TPU-native adaptation of the SSD algorithm: because the decay is
a *scalar per head*, the intra-chunk interaction matrix

    L[t, s] = exp(acum_t - acum_s) for s <= t (else 0),  acum = cumsum(dt * A)

is formed directly from the pairwise difference of the chunk-local cumsum —
every exponent is <= 0, so the factorization is f32-stable — and the chunk is
computed with three MXU matmuls instead of T rank-1 VPU updates:

    intra:  Y  = (L o (C B^T)) @ (dt * X)              (ct,ct)@(ct,P)
    inter:  Y += exp(acum)[:, None] * (C @ h_prev^T)   (ct,N)@(N,P)
    state:  h' = exp(acum_T) h_prev + (dtX)^T @ (B o exp(acum_T - acum))

Tiling: grid = (B*H, T/chunk), chunks sequential with the (P, N) state in
VMEM scratch. B/C are stored per-group (G groups) and mapped to heads in the
BlockSpec index map — no HBM-side repeat.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba2_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                   y_ref, hout_ref, h_scr, *,
                   chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # (ct, P)
    dt = dt_ref[0].astype(jnp.float32)        # (ct, 1)
    A = a_ref[0].astype(jnp.float32)          # (1,) scalar decay rate
    Bm = b_ref[0].astype(jnp.float32)         # (ct, N)
    Cm = c_ref[0].astype(jnp.float32)         # (ct, N)

    la = dt * A                               # (ct, 1) per-step log decay <= 0
    acum = jnp.cumsum(la, axis=0)             # (ct, 1) inclusive
    # L[t, s] = exp(acum_t - acum_s + la_s)   for s <= t; la_s restores the
    # "decay applied after add" convention: contribution of s to h_t is
    # exp(sum_{r=s+1..t} la_r) = exp(acum_t - acum_s).
    diff = acum - acum.T                      # (ct, ct), [t,s] = acum_t - acum_s
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)

    dtx = dt * x                              # (ct, P)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (ct, ct)
    y = jax.lax.dot_general(L * cb, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (ct, P)
    # inter-chunk: y_t += exp(acum_t) * C_t @ h_prev^T
    h_prev = h_scr[...]                        # (P, N)
    y += jnp.exp(acum) * jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)    # (ct, P)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update
    total = acum[-1:, :]                       # (1, 1)
    w = jnp.exp(total - acum)                  # (ct, 1), exponents <= 0
    h_new = jnp.exp(total) * h_prev + jax.lax.dot_general(
        dtx, Bm * w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # (P, N)
    h_scr[...] = h_new

    @pl.when(ic == n_chunks - 1)
    def _finalize():
        hout_ref[0] = h_scr[...]


def mamba2_fwd(
    x: jnp.ndarray,     # (BH, T, P)
    dt: jnp.ndarray,    # (BH, T, 1)
    A: jnp.ndarray,     # (BH, 1)
    Bm: jnp.ndarray,    # (BG, T, N)  per-group
    Cm: jnp.ndarray,    # (BG, T, N)
    h0: jnp.ndarray,    # (BH, P, N)
    *,
    n_heads: int,
    n_groups: int,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    BH, T, P = x.shape
    N = Bm.shape[-1]
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk
    rep = n_heads // n_groups

    def head_seq(last):
        return pl.BlockSpec((1, chunk, last), lambda bh, ic: (bh, ic, 0))

    def group_seq(last):
        def idx(bh, ic):
            b, h = bh // n_heads, bh % n_heads
            return (b * n_groups + h // rep, ic, 0)
        return pl.BlockSpec((1, chunk, last), idx)

    kernel = functools.partial(_mamba2_kernel, chunk=chunk, n_chunks=n_chunks)
    y, hout = pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[
            head_seq(P), head_seq(1),
            pl.BlockSpec((1, 1), lambda bh, ic: (bh, 0)),
            group_seq(N), group_seq(N),
            pl.BlockSpec((1, P, N), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[head_seq(P), pl.BlockSpec((1, P, N), lambda bh, ic: (bh, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sfprompt_mamba2_ssd",
    )(x, dt, A, Bm, Cm, h0)
    return y, hout
