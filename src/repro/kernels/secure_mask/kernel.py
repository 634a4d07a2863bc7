"""Fused fixed-point encode + pairwise-mask-add as a Pallas TPU kernel.

One VMEM pass per block of the flattened client delta: encode x into the
uint32 ring, then fold in every pairwise mask stream generated ON-CORE with
`pltpu.prng_random_bits` — the mask bits never exist in HBM, only their sum
folded into the upload. Grid over row blocks; each (pair, block) stream is
seeded with (pair seed, block index) so blocks draw disjoint streams and
the server's dropout-recovery pass (same seeds, x = 0) regenerates them
exactly.

Unlike kernels/quant, the PRG here is deliberately NOT host-fed: the mask
stream per client is O(n_pairs * n) bits — materializing it defeats the
one-pass point. The pure-jnp ref uses a different PRG (threefry); that is
fine because mask bits only ever need to cancel within one impl (see
ref.py). pltpu PRNG has no interpret-mode lowering in this JAX, so CPU CI
exercises the ref path and this kernel validates on real TPUs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.secure_mask.ref import FRAC_BITS, SAT

LANES = 128


def _masked_encode_kernel(seeds_ref, signs_ref, x_ref, o_ref, *,
                          frac_bits: int, n_pairs: int):
    # ---- fixed-point encode (saturating two's complement)
    q = jnp.round(x_ref[...].astype(jnp.float32) * (2.0 ** frac_bits))
    q = jnp.clip(q, -SAT, SAT)
    # |q| <= SAT < 2^31, so the int32 cast is exact and its two's-complement
    # bits are the ring value (the TPU has no float -> uint32 cast)
    acc = pltpu.bitcast(q.astype(jnp.int32), jnp.uint32)

    # ---- fold in each pairwise mask stream, generated on-core
    blk = pl.program_id(0)
    for j in range(n_pairs):          # n_pairs is static (K - 1), unrolled
        pltpu.prng_seed(seeds_ref[j], blk)
        bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
        sign = signs_ref[j]
        m = jnp.where(sign < 0, jnp.uint32(0) - bits, bits)
        acc = acc + jnp.where(sign == 0, jnp.uint32(0), m)
    o_ref[...] = acc


def masked_encode_fwd(x: jnp.ndarray, seeds: jnp.ndarray,
                      signs: jnp.ndarray, *, frac_bits: int = FRAC_BITS,
                      block_n: int = 8, interpret: bool = False):
    """x (N, LANES) f32, seeds/signs (n_pairs,) — N % block_n == 0.
    Returns the masked uint32 upload (N, LANES)."""
    N, D = x.shape
    n_pairs = seeds.shape[0]
    kernel = functools.partial(_masked_encode_kernel, frac_bits=frac_bits,
                               n_pairs=n_pairs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // block_n,),
        in_specs=[pl.BlockSpec((block_n, D), lambda i, *_: (i, 0))],
        out_specs=pl.BlockSpec((block_n, D), lambda i, *_: (i, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sfprompt_secure_masked_encode",
    )(seeds.astype(jnp.uint32), signs.astype(jnp.int32), x)
