"""Public int8 wire quantize/dequantize ops with impl dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import row_blocks
from repro.kernels.quant import ref
from repro.kernels.quant.kernel import LANES, dequantize_fwd, quantize_fwd


def _resolve(impl: str) -> str:
    if impl in ("auto", "analysis"):
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


BLOCK_ROWS = 256


@functools.partial(jax.jit, static_argnames=("impl",))
def quantize_int8(x: jnp.ndarray, u: jnp.ndarray, *, impl: str = "auto"):
    """Row-wise symmetric int8 quantization with stochastic rounding.

    x: (N, D) float; u: uniform noise in [0,1) broadcastable to (N, D)
    (pass 0.5 for deterministic round-to-nearest).
    Returns (values (N, D) int8, scales (N, 1) f32).
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.quantize(x, u)
    N, D = x.shape
    u = jnp.broadcast_to(jnp.asarray(u, jnp.float32), x.shape)
    block_n, n_pad = row_blocks(N, BLOCK_ROWS)
    pad = ((0, n_pad - N), (0, (-D) % LANES))
    values, scales = quantize_fwd(jnp.pad(x, pad), jnp.pad(u, pad),
                                  block_n=block_n,
                                  interpret=(impl == "interpret"))
    return values[:N, :D], scales[:N, :1]


@functools.partial(jax.jit, static_argnames=("impl", "dtype"))
def dequantize_int8(values: jnp.ndarray, scales: jnp.ndarray, *,
                    dtype=jnp.float32, impl: str = "auto"):
    """values (N, D) int8, scales (N, 1) f32 -> (N, D) dtype."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.dequantize(values, scales, dtype)
    N, D = values.shape
    block_n, n_pad = row_blocks(N, BLOCK_ROWS)
    values = jnp.pad(values, ((0, n_pad - N), (0, (-D) % LANES)))
    scales = jnp.pad(
        jnp.broadcast_to(scales.astype(jnp.float32), (N, LANES)),
        ((0, n_pad - N), (0, 0)))
    out = dequantize_fwd(values, scales, dtype=dtype, block_n=block_n,
                         interpret=(impl == "interpret"))
    return out[:N, :D]
