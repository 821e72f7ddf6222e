"""Training batches: a fresh seeded batch for every step, tokens drawn
from a Zipf law over the vocabulary (rank r with probability ~ r^-a), so
that there is something to learn (the unigram statistics) and the loss
must fall, which uniform random tokens would not allow.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


class ZipfBatches:
    def __init__(self, params: Dict[str, Any], seed: int, vocab: int):
        self.seed = seed
        self.micro_batch = int(params["micro_batch_per_chip"])
        self.seq_len = int(params["seq_len"])
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(params["zipf_a"])
        self._cdf = np.cumsum(p / p.sum())
        # which token has which rank is itself seeded
        self._ids = np.random.default_rng([seed, 2]).permutation(vocab)
        self.vocab = vocab

    def tokens_per_step(self, chips: int) -> int:
        return chips * self.micro_batch * self.seq_len

    def batch(self, step: int, chips: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 3, step])
        u = rng.random((chips * self.micro_batch, self.seq_len))
        ranks = np.minimum(np.searchsorted(self._cdf, u), self.vocab - 1)
        return self._ids[ranks].astype(np.int32)


def build(params: Dict[str, Any], seed: int, vocab: int) -> ZipfBatches:
    return ZipfBatches(params, seed, vocab)
