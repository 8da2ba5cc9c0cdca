"""The training traffic: token batches made from ``--seed``.

A traffic file (``bench/traffic/<name>.json``) fixes the batch, the
sequence length and the split into microbatches and token slices; this
generator fills it.  Step ``i`` of a run gets its own stream (the seed and
the step), so every row of every step differs, and the same seed gives the
same batches on every run.  Tokens are uniform over the vocabulary.
"""
from __future__ import annotations

import numpy as np


class TokenSource:
    """The ``tokens_at(step, shard, shape)`` source the program's
    ``DataPipeline`` reads."""

    def __init__(self, vocab_size: int, seed: int):
        self.vocab_size, self.seed = int(vocab_size), int(seed)

    def tokens_at(self, step: int, shard: int, shape) -> np.ndarray:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, int(step), int(shard)]))
        return rng.integers(0, self.vocab_size, shape, dtype=np.int32)


def batch(source: TokenSource, step: int, rows: int, seq: int):
    """(tokens, labels) of step ``step``: rows of seq + 1 tokens, shifted."""
    toks = source.tokens_at(step, 0, (rows, seq + 1))
    return toks[:, :-1], toks[:, 1:]
