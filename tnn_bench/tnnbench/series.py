"""Frozen copy of the seeded time-series generator.

Copied from the program's ``repro/data/series.py`` so that the benchmark's
traffic stays the same when the program's data module changes. Each of
``classes`` classes is a fixed template of a few smooth Gaussian bumps over
``length`` samples (a pure function of the shape); a series is its class's
template shifted in time, scaled in amplitude, with white noise added, and
z-normalised per series as UCR series are. ``series(n, length, classes,
seed)`` is a pure function of its arguments.
"""
from __future__ import annotations

from typing import Sequence, Tuple, Union

import numpy as np

BUMPS = 3            # Gaussian bumps per class template
MAX_SHIFT = 0.05     # largest time shift, as a share of the length
SCALE = (0.8, 1.2)   # amplitude scale range
NOISE = 0.1          # white-noise standard deviation, before z-normalising


def templates(length: int, classes: int) -> np.ndarray:
    """Per class, ``BUMPS`` rows of (centre, width, amplitude) in samples:
    (classes, BUMPS, 3) float64, a pure function of the shape."""
    rng = np.random.default_rng([length, classes])
    centre = rng.uniform(0.1, 0.9, (classes, BUMPS)) * length
    width = rng.uniform(0.02, 0.1, (classes, BUMPS)) * length
    amp = rng.uniform(0.5, 1.5, (classes, BUMPS)) * rng.choice(
        [-1.0, 1.0], (classes, BUMPS))
    return np.stack([centre, width, amp], axis=-1)


def series(n: int, length: int, classes: int,
           seed: Union[int, Sequence[int]] = 0
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (series (n, length) float32, z-normalised per series; labels
    (n,) int32)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, n)
    shift = rng.uniform(-MAX_SHIFT, MAX_SHIFT, (n, 1, 1)) * length
    scale = rng.uniform(*SCALE, (n, 1, 1))
    tpl = templates(length, classes)[labels]                  # (n, BUMPS, 3)
    t = np.arange(length, dtype=np.float64)[None, None, :]
    centre, width, amp = (tpl[..., i:i + 1] for i in range(3))
    bumps = amp * scale * np.exp(-0.5 * ((t - centre - shift) / width) ** 2)
    x = bumps.sum(axis=1) + rng.normal(0.0, NOISE, (n, length))
    x = (x - x.mean(axis=1, keepdims=True)) / x.std(axis=1, keepdims=True)
    return x.astype(np.float32), labels.astype(np.int32)
