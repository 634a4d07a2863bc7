"""Int8 stochastic quantize / dequantize as Pallas TPU kernels.

The wire codecs (runtime/codec.py) push every smashed activation and every
cut-layer gradient through this pair, so it sits on the head->body and
body->tail boundaries of phase-2 training AND the serving path — one HBM
pass each way.

Noise comes in as an explicit uniform input rather than pltpu.prng_*: the
host generates the bits from the protocol's PRNG key, which keeps the kernel
bit-identical to the pure-jnp ref (same noise -> same int8 payload) and
portable to interpret mode, where this JAX has no TPU PRNG lowering.

Tiling: grid over row blocks; a row (one token of the smashed tensor) never
spans tiles, so the per-row max/scale lives entirely in VMEM registers.
Scales are emitted LANES-wide (column 0 meaningful) like the el2n kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
EPS = 1e-8
QMAX = 127.0


def _quantize_kernel(x_ref, u_ref, v_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                  # (block_n, D)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / QMAX, EPS)               # (block_n, 1)
    q = jnp.floor(x / scale + u_ref[...].astype(jnp.float32))
    v_ref[...] = jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _dequantize_kernel(v_ref, s_ref, o_ref, *, dtype):
    scale = s_ref[:, :1]
    o_ref[...] = (v_ref[...].astype(jnp.float32) * scale).astype(dtype)


def quantize_fwd(x: jnp.ndarray, u: jnp.ndarray, *, block_n: int = 256,
                 interpret: bool = False):
    """x (N, D) float, u (N, D) uniform noise; N % block_n == 0.
    Returns (values (N, D) int8, scales (N, LANES) f32, col 0 meaningful)."""
    N, D = x.shape
    values, scales = pl.pallas_call(
        _quantize_kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((block_n, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), jnp.int8),
            jax.ShapeDtypeStruct((N, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sfprompt_wire_quantize",
    )(x, u)
    return values, scales


def dequantize_fwd(values: jnp.ndarray, scales: jnp.ndarray, *,
                   dtype=jnp.float32, block_n: int = 256,
                   interpret: bool = False):
    """values (N, D) int8, scales (N, LANES) f32 -> (N, D) dtype."""
    N, D = values.shape
    kernel = functools.partial(_dequantize_kernel, dtype=dtype)
    return pl.pallas_call(
        kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((block_n, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="sfprompt_wire_dequantize",
    )(values, scales)
