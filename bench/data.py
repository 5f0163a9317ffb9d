"""The benchmark's token traffic: a copy of the trainer's synthetic Markov
generator (``repro.data.pipeline.SyntheticDataset``), kept here so that the
yardstick cannot move when the program's generator changes.

A noisy order-2 Markov chain over ``n_states`` token ids: the transition
table comes from ``seed + 1``, each step's batch from ``(seed, step)``. A test
holds the copy equal to the trainer's generator batch for batch.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


class MarkovTokens:
    def __init__(self, vocab: int, batch: int, seq: int, seed: int,
                 n_states: int = 64):
        self.batch, self.seq, self.seed = batch, seq, seed
        self.n_states = min(n_states, vocab)
        table = np.random.default_rng(seed + 1).integers(
            0, self.n_states, size=(self.n_states, self.n_states))
        self._flat = np.ascontiguousarray(table).reshape(-1)

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """Tokens and next-token labels of global step ``step``."""
        rng = np.random.default_rng((self.seed, step))
        b, s, n = self.batch, self.seq, self.n_states
        out = rng.integers(0, n, size=(b, s + 1))
        if s >= 2:
            masks = rng.random((s - 1, b)) < 0.9
            for t in range(2, s + 1):
                nxt = self._flat[out[:, t - 1] * n + out[:, t - 2]]
                np.copyto(out[:, t], nxt, where=masks[t - 2])
        out = out.astype(np.int32)
        return {"tokens": out[:, :-1], "labels": out[:, 1:]}
