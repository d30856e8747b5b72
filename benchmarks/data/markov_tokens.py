"""Recipe ``markov_tokens``: seeded order-1 Markov chains of token ids.

Every id has ``fanout`` successors, drawn once from the seed, taken with
probabilities 1/2, 1/4, ... (the last two equal): a chain a model can learn,
so the loss falls from ``ln(vocabulary)`` towards the chain's entropy (1.21
nats at fanout 4). A site's chain is the base table with a seeded
``site_share`` of its rows drawn again: neighbours agree on most of the
language and differ on some, as sites' text does.

The program receives only the arrays: ``[n, seq_len + 1]`` int32 ids (all
inside the held slice of the vocabulary, ``spec["vocab_rows"]``) and int32
labels that the next-token task does not read.
"""

from __future__ import annotations

import numpy as np


def _cumulative(fanout: int) -> np.ndarray:
    p = 0.5 ** np.arange(1, fanout + 1)
    p[-1] = p[-2] if fanout > 1 else 1.0
    return np.cumsum(p / p.sum())


def make_sites(spec: dict, sample_shape: tuple, num_sites: int, seed: int):
    """``[(inputs, labels), ...]``, one pair per site."""
    n, length = int(spec["subjects_per_site"]), int(sample_shape[0])
    vocab, fanout = int(spec["vocab_rows"]), int(spec.get("fanout", 4))
    share = float(spec.get("site_share", 0.25))
    base = np.random.default_rng([seed, 0x70C]).integers(
        0, vocab, (vocab, fanout), dtype=np.int32)
    cum = _cumulative(fanout)
    out = []
    for site in range(num_sites):
        rng = np.random.default_rng([seed, site, 0x70C])
        table = base.copy()
        own = rng.random(vocab) < share
        table[own] = rng.integers(0, vocab, (int(own.sum()), fanout),
                                  dtype=np.int32)
        ids = np.empty((n, length), np.int32)
        ids[:, 0] = rng.integers(0, vocab, n)
        picks = np.searchsorted(cum, rng.random((n, length - 1))).astype(np.int32)
        rows = np.arange(n)
        for t in range(1, length):
            ids[:, t] = table[ids[rows, t - 1], picks[:, t - 1]]
        out.append((ids, np.zeros((n,), np.int32)))
    return out
