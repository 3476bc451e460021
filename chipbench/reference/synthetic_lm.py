"""The training token stream, regenerated by the reference on its own.

The program's trainer draws each round's batches from its synthetic stream
(``data/lm.py``): per (seed, worker, round), rows of a noisy affine
recurrence over the vocabulary, t_{i+1} = (31 t_i + 17) mod V with
probability 0.9 and a uniform draw otherwise, from numpy's
``default_rng(SeedSequence([seed, worker, round]))``. This is the same
stream written out again, so that the reference compares the program's
whole input path instead of taking its batches.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

MULT, ADD, NOISE = 31, 17, 0.1


def rows(seed: int, worker: int, step: int, batch: int, seq: int,
         vocab: int) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(np.random.SeedSequence([seed, worker, step]))
    toks = np.empty((batch, seq + 1), np.int64)
    for b in range(batch):
        toks[b, 0] = rng.integers(0, vocab)
        for i in range(seq):
            if rng.uniform() < NOISE:
                toks[b, i + 1] = rng.integers(0, vocab)
            else:
                toks[b, i + 1] = (toks[b, i] * MULT + ADD) % vocab
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}
