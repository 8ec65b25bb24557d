"""The token stream a cell trains on, made again from the seed.

The program's input pipeline makes its records from ``(seed, step)``;
this module makes the same batches without it, by the stream's stated
rule: a NumPy generator seeded with ``SeedSequence([seed, step])`` draws
``batch x (seq_len + 1)`` uniform int32 token ids below the vocabulary
size; the inputs are all but the last column and the labels all but the
first. Every batch the pipeline delivers is compared with this one."""

from __future__ import annotations

import numpy as np


def step_batch(seed: int, step: int, vocab: int, batch: int, seq: int):
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    toks = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
