"""Token batches for a language-model job, from a seed.

Documents of lognormal length, token ids Zipf-distributed over a seeded
permutation of the vocabulary, each document ended by the end-of-text id,
all concatenated and cut into rows of the context length with no mask
across documents (GPT-2's own packing).
"""

from __future__ import annotations

import numpy as np


def pool(seed: int, params: dict, *, rows: int, seq_len: int,
         vocab_size: int, eos_id: int) -> list[np.ndarray]:
    """``params['pool_batches']`` distinct ``[rows, seq_len]`` int32
    batches. The same seed gives the same batches."""
    rng = np.random.default_rng([seed, 0x70C])
    n_batches = int(params["pool_batches"])
    need = n_batches * rows * seq_len

    # Zipf over ranks 1..V-1 (the end-of-text id is kept out of the
    # body), ranks mapped to ids by a seeded permutation.
    body_ids = np.delete(np.arange(vocab_size), eos_id)
    rng.shuffle(body_ids)
    weights = 1.0 / np.arange(1, body_ids.size + 1) ** float(
        params["zipf_exponent"])
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]

    mu, sigma = np.log(float(params["doc_len_median"])), float(
        params["doc_len_sigma"])
    stream = np.empty(need, np.int32)
    filled = 0
    while filled < need:
        n_docs = max(16, int(1.2 * (need - filled) / np.exp(mu + sigma**2 / 2)))
        lens = np.maximum(1, rng.lognormal(mu, sigma, n_docs).astype(np.int64))
        ends = np.cumsum(lens + 1)  # each document plus its end-of-text
        total = int(ends[-1])
        chunk = body_ids[np.searchsorted(cdf, rng.random(total))].astype(
            np.int32)
        chunk[ends - 1] = eos_id
        take = min(total, need - filled)
        stream[filled:filled + take] = chunk[:take]
        filled += take
    return list(stream.reshape(n_batches, rows, seq_len))
