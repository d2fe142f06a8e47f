"""Traffic is a pure function of the run's seed, and every seed offers the
same work."""
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from tnnbench import common, digits, seeds  # noqa: E402

BIG = 2 ** 33 + 17


def test_frames_and_seeds_are_deterministic():
    x1, y1 = digits.digits(8, seed=[BIG, 1])
    x2, y2 = digits.digits(8, seed=[BIG, 1])
    x3, _ = digits.digits(8, seed=[BIG, 2])
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert not np.array_equal(x1, x3)
    assert seeds.jax_seed(BIG, 4) == seeds.jax_seed(BIG, 4) < 2 ** 31
    assert seeds.jax_seed(BIG, 4) != seeds.jax_seed(BIG, 5)


def test_stream_rows_wrap():
    x = np.arange(40)[:, None]
    assert common.rows(x, 16, 0)[:, 0].tolist() == list(range(16))
    assert common.rows(x, 16, 2)[:, 0].tolist() == list(range(32, 40)) + list(range(8))
