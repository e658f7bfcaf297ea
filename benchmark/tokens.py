"""The benchmark's own token generator: every sequence of a run from
``--seed``, the same sizes for every seed.

Tokens follow a truncated Zipf law over the vocabulary (frequency
proportional to 1 / (rank + 10)), as a unigram model of text: the loss
has something to learn in the first steps, and every seed draws the
same number of sequences of the same length, only other tokens."""

import numpy as np


def make_tokens(seed, sequences, seq, vocab):
    """``(tokens, targets)``, both ``[sequences, seq]`` int32; a target
    is the token that follows (drawn as one ``seq + 1`` long row)."""
    rng = np.random.default_rng(int(seed))
    weights = 1.0 / (np.arange(vocab, dtype=np.float64) + 10.0)
    cdf = np.cumsum(weights / weights.sum())
    draws = rng.random((int(sequences), int(seq) + 1))
    rows = np.minimum(np.searchsorted(cdf, draws), vocab - 1).astype(
        np.int32)
    return (np.ascontiguousarray(rows[:, :-1]),
            np.ascontiguousarray(rows[:, 1:]))
