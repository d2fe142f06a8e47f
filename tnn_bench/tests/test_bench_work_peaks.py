"""The required-work yardstick at the prototype's shapes, and the table of
peaks."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from tnnbench import peaks, work  # noqa: E402


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_prototype_wave_work():
    proto = cfg("tnn-proto")
    assert work.synapses(proto) == 315_000
    fwd = work.wave(proto, 16, learn=False)
    assert fwd.ops == 80_640_000
    # spikes in + weights read + last layer's times out
    assert fwd.bytes == 320_000 + 315_000 + 100_000
    learn = work.wave(proto, 16, learn=True)
    assert learn.ops == 80_640_000 + 3 * 315_000 * 16 + 2 * 315_000
    assert learn.bytes == 320_000 + 2 * 315_000 + 220_000


def test_cascade_and_totals():
    deep = dict(cfg("tnn-proto"), widths=[12, 12, 10])
    assert work.synapses(deep) == 405_000
    one = work.wave(deep, 16, learn=True)
    assert one.times(4).ops == 4 * one.ops and one.times(0).bytes == 0


def test_peaks_refuse_an_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes_per_s"] == 819e9
    least = work.wave(cfg("tnn-proto"), 16, learn=True).least_s(v5e)
    assert least == pytest.approx(1_170_000 / 819e9)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
