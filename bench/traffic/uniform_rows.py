"""Training rows of uniform token ids, ``seq_len + 1`` a row, fresh for
every step and seed."""
from __future__ import annotations

import numpy as np

import trafficlib


def train_batch(mix: dict, seed: int, step: int, rows: int, vocab: int
                ) -> np.ndarray:
    """Step ``step``'s token batch, (rows, seq_len + 1) int32: inputs are
    ``[:, :-1]`` and next-token labels ``[:, 1:]``."""
    r = trafficlib.rng(seed, 4, step)
    return r.integers(0, vocab, size=(rows, int(mix["seq_len"]) + 1),
                      dtype=np.int32)
