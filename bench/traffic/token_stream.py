"""Seeded synthetic token stream for training jobs.

A copy of the program's ``TokenStream`` (``src/repro/data/pipeline.py``),
kept with the benchmark so that no change to the program moves the
yardstick: Markov-chain token sequences, every batch a pure function of
(seed, step).
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _chain_table(seed: int, n_states: int, alpha: float) -> np.ndarray:
    """Row-stochastic transition table over a small state space."""
    rng = np.random.default_rng(seed + 7919)
    logits = rng.gumbel(size=(n_states, n_states)) * alpha
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    return p / p.sum(axis=1, keepdims=True)


class TokenStream:
    """batch(step) -> (batch, seq) int32 tokens; pure in (seed, step)."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int,
                 chain_alpha: float = 6.0, n_states: int = 64):
        self.seed, self.batch_size, self.seq, self.vocab = seed, batch, seq, vocab
        self.n_states = n_states
        self._cum = np.cumsum(_chain_table(seed, n_states, chain_alpha), axis=1)

    def batch(self, step: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed << 20) ^ step)
        b, s = self.batch_size, self.seq
        states = np.empty((b, s), np.int64)
        states[:, 0] = rng.integers(0, self.n_states, b)
        u = rng.random((b, s))
        for t in range(1, s):
            rows = self._cum[states[:, t - 1]]
            states[:, t] = (u[:, t:t + 1] < rows).argmax(axis=1)
        # states into the vocabulary by a step-independent scatter
        return (states * 2654435761 % self.vocab).astype(np.int32)


def make(params: Dict, seed: int, vocab: int) -> TokenStream:
    """The generator a training job file names (``"generator"``)."""
    return TokenStream(seed, params["batch"], params["seq"], vocab,
                       **params.get("stream", {}))
