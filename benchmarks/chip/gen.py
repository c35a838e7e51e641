"""Traffic generation: every input a run feeds the program, from its seed.

One general generator per kind of work, read from a mix file's
parameters.  Sizes (batch, sequence length) are fixed by the mix alone,
so every run seed gets the same work; the run seed only draws the token
ids.  That keeps the work of a cell the same from seed to seed while the
inputs differ.

The Zipf token stream is the program's ``data/pipeline.SyntheticLMData``
stream, copied here so the yardstick does not move with the program.
"""
from __future__ import annotations

import numpy as np

ZIPF_EXPONENT = 2.5


def rng(*words: int) -> np.random.Generator:
    """A generator keyed by whole numbers of any size (seeds run past 2**31)."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) % (1 << 63) for w in words]))


def key32(seed: int) -> int:
    """A 32-bit PRNG key word derived from a seed of any size."""
    return int(np.random.SeedSequence(int(seed) % (1 << 63))
               .generate_state(1, np.uint32)[0])


def zipf_tokens(g: np.random.Generator, shape, vocab: int) -> np.ndarray:
    """Zipf-like ids: the inverse CDF of a power law over [0, vocab)."""
    u = g.random(shape)
    return np.minimum((vocab * u ** ZIPF_EXPONENT).astype(np.int32),
                      vocab - 1)


def train_batch(seed: int, step: int, batch: int, seq: int,
                vocab: int) -> dict:
    """Step ``step``'s rows: tokens and next-token labels, [batch, seq]."""
    toks = zipf_tokens(rng(seed, step), (batch, seq + 1), vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
