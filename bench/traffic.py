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

A `closed` mix is split serving under a closed loop: `callers` callers,
each with one request in flight at a time, all of tenant `tenant`. Each
caller's stream repeats blocks of every (prompt length, output length)
pair of `prompt_lengths` x `output_lengths`, each block in its own order
drawn from the seed, so every seed sends the same sizes in another order;
prompt tokens are uniform over the vocabulary. The check requests served
in set-up are one per slot: prompt lengths and output lengths each taken
in descending order and cycled, so that they hold the mix's longest
request, every prompt length and every output length.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

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


def caller_stream(mix: Dict, seed: int, caller: int, vocab_size: int
                  ) -> Iterator[Tuple[np.ndarray, int]]:
    """The endless (prompt tokens, output length) stream of one caller."""
    g = rng(seed, 5, caller)
    pairs = [(p, n) for p in mix["prompt_lengths"]
             for n in mix["output_lengths"]]
    while True:
        for i in g.permutation(len(pairs)):
            p, n = pairs[i]
            yield g.integers(0, vocab_size, p, dtype=np.int64).astype(
                np.int32), int(n)


def closed_loop(mix: Dict, seed: int, vocab_size: int
                ) -> List[Iterator[Tuple[np.ndarray, int]]]:
    """One stream per caller of a `closed` mix."""
    return [caller_stream(mix, seed, c, vocab_size)
            for c in range(mix["callers"])]


def check_requests(mix: Dict, seed: int, vocab_size: int, slots: int
                   ) -> List[Tuple[np.ndarray, int]]:
    """The (prompt tokens, output length) of the check requests, one per
    slot."""
    g = rng(seed, 6)
    prompts = sorted(mix["prompt_lengths"], reverse=True)
    outputs = sorted(mix["output_lengths"], reverse=True)
    return [(g.integers(0, vocab_size, prompts[i % len(prompts)],
                        dtype=np.int64).astype(np.int32),
             int(outputs[i % len(outputs)])) for i in range(slots)]
