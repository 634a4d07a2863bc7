"""The general traffic generator: every mix is a data file of parameters
(`bench/traffic/<mix>.json`) that these functions read.

A `rounds` mix is federated training. A population of `clients`, each
with a true size drawn from the multiset `client_sizes` (repeated to
`clients` entries and shuffled per seed, so every seed gets the same sizes
in another order); each client's rows are its own samples, repeated in
order to `samples_per_client` rows so that the cohort stacks. The program
samples `cohort` clients per round and weights FedAvg by true size.
Further parameters: batch, local_epochs, secure (and frac_bits, mask_seed),
check_rounds.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        (seed & 0xFFFFFFFF, seed >> 32) + tuple(stream)))


def population(mix: Dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(rows, sizes): rows is (clients, samples_per_client) indices into a
    dataset of sizes.sum() samples, client c owning the block of sizes[c]
    samples after those of clients 0..c-1; sizes are the true sizes."""
    n, width = mix["clients"], mix["samples_per_client"]
    sizes = rng(seed, 3).permutation(np.resize(
        np.asarray(mix["client_sizes"], np.int64), n))
    if sizes.min() < 1 or sizes.max() > width:
        raise ValueError(f"client sizes must lie in [1, {width}], got "
                         f"{sorted(set(sizes.tolist()))}")
    start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    rows = start[:, None] + np.arange(width)[None, :] % sizes[:, None]
    return rows, sizes
