"""RWKV-6 recurrence as a chunked Pallas TPU kernel.

Tiling: grid = (B*H, T/chunk); chunks are sequential, carrying the (K, V)
state matrix in VMEM scratch — HBM sees each of r/k/v/w exactly once and the
state never leaves VMEM between chunks (vs. a lax.scan whose carry round-trips
HBM every step). Within a chunk the recurrence is stepped exactly
(rank-1 state updates on the VPU); this is numerically exact for arbitrary
data-dependent decays, unlike the factorized GLA matmul form whose
exp(-cumsum) terms overflow f32 for strong decays. (A sub-chunk-stabilized
matmul intra-chunk path is the known next optimization; see EXPERIMENTS.md
§Perf.)

Head sizes are 64 in RWKV-6, so the state tile is (64, 64) f32 = 16 KiB —
VMEM-resident with room for double-buffered input chunks.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                  y_ref, sout_ref, s_scr, *,
                  chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)   # (chunk, K)
    k = k_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)   # (chunk, V)
    u = u_ref[0].astype(jnp.float32)   # (K,)

    def step(t, carry):
        S, y = carry                                    # (K,V), (chunk,V)
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)   # (1, K)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)   # (1, V)
        bonus = jnp.sum(rt * u[None, :] * kt)           # scalar
        yt = jax.lax.dot_general(rt, S, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) \
            + bonus * vt                                 # (1, V)
        y = jax.lax.dynamic_update_slice_in_dim(y, yt, t, 0)
        S = jnp.exp(wt).T * S + kt.T * vt               # (K,V)
        return S, y

    S, y = jax.lax.fori_loop(
        0, chunk, step,
        (s_scr[...], jnp.zeros_like(y_ref[0], jnp.float32)))
    s_scr[...] = S
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finalize():
        sout_ref[0] = s_scr[...]


def rwkv6_fwd(
    r: jnp.ndarray,       # (BH, T, K)
    k: jnp.ndarray,
    v: jnp.ndarray,       # (BH, T, V)
    w: jnp.ndarray,       # (BH, T, K) log-decay
    u: jnp.ndarray,       # (BH, K)
    s0: jnp.ndarray,      # (BH, K, V)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    BH, T, K = r.shape
    V = v.shape[-1]
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk

    seq_spec = lambda last: pl.BlockSpec((1, chunk, last), lambda bh, ic: (bh, ic, 0))
    head_spec = lambda *dims: pl.BlockSpec((1,) + dims, lambda bh, ic: (bh,) + (0,) * len(dims))

    kernel = functools.partial(_rwkv6_kernel, chunk=chunk, n_chunks=n_chunks)
    y, sout = pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[
            seq_spec(K), seq_spec(K), seq_spec(V), seq_spec(K),
            head_spec(K), head_spec(K, V),
        ],
        out_specs=[seq_spec(V), head_spec(K, V)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, V), r.dtype),
            jax.ShapeDtypeStruct((BH, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sfprompt_rwkv6_scan",
    )(r, k, v, w, u, s0)
    return y, sout
