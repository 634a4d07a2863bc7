"""Public fused masked-encode ops with impl dispatch.

The contract the secure aggregator relies on: within ONE impl, the stream
`summed_mask(seeds, signs, n)` is a pure function of its arguments, so the
masks a client folded into its upload are exactly the masks the server
regenerates for dropout recovery. Across impls the streams differ (threefry
ref vs pltpu TPU PRNG) but the cohort ring sum is impl-independent — masks
cancel before anything is decoded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import row_blocks
from repro.kernels.secure_mask import ref
from repro.kernels.secure_mask.kernel import LANES, masked_encode_fwd
from repro.kernels.secure_mask.ref import (FRAC_BITS, decode,  # noqa: F401
                                           encode)


def _resolve(impl: str) -> str:
    if impl in ("auto", "analysis"):
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def ring_size(n: int) -> int:
    """Flattened uploads are padded to a LANES multiple so the Pallas path
    tiles cleanly; the pad rides the wire too (masks cover it), so both the
    meter and the analytical model count the PADDED length."""
    return n + (-n) % LANES


BLOCK_ROWS = 512


def _pallas_masked_encode(x, seeds, signs, frac_bits, interpret):
    """The kernel over the (n // LANES, LANES) view of x, its rows padded
    up to whole blocks and sliced back. The block size is a pure function
    of n, so the uploads and the server's recovery pass draw the same
    per-block streams."""
    n = x.shape[0]
    rows = n // LANES
    block_n, n_rows = row_blocks(rows, BLOCK_ROWS)
    x2 = jnp.pad(x.reshape(rows, LANES), ((0, n_rows - rows), (0, 0)))
    out = masked_encode_fwd(x2, seeds, signs, frac_bits=frac_bits,
                            block_n=block_n, interpret=interpret)
    return out[:rows].reshape(n)


@functools.partial(jax.jit, static_argnames=("frac_bits", "impl"))
def masked_encode(x: jnp.ndarray, seeds: jnp.ndarray, signs: jnp.ndarray, *,
                  frac_bits: int = FRAC_BITS, impl: str = "auto"):
    """One client's secure upload: encode(x) + sum_j sign_j * PRG(seed_j).

    x: (n,) f32 with n % LANES == 0 (see ring_size); seeds (J,) uint32,
    signs (J,) int32 in {-1, 0, +1}. Returns (n,) uint32.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.masked_encode(x, seeds, signs, frac_bits)
    return _pallas_masked_encode(x, seeds, signs, frac_bits,
                                 interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("n", "frac_bits", "impl"))
def summed_mask(seeds: jnp.ndarray, signs: jnp.ndarray, n: int, *,
                frac_bits: int = FRAC_BITS, impl: str = "auto"):
    """The pure mask stream (encode of zero) — the server's dropout-recovery
    reconstruction. MUST ride the same impl as the uploads it corrects."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.summed_mask(seeds, signs, n)
    return _pallas_masked_encode(jnp.zeros((n,), jnp.float32), seeds, signs,
                                 frac_bits, interpret=(impl == "interpret"))
