"""The independent streams a run draws from its ``--seed``.

A seed is any non-negative integer, however large; each named use of it
(frames, weights, the STDP key) gets a stream of its own, so that adding a
use never shifts another.
"""
from __future__ import annotations

import numpy as np

# streams of the run's seed, one per use
TRAIN_IMAGES, WEIGHTS, STDP_KEY = 1, 4, 5


def jax_seed(seed: int, stream: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` from the run's seed."""
    word = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return int(word[0] >> 1)
