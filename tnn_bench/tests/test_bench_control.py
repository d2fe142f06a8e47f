"""The comparison's control (the plain reference in bfloat16) and faults
planted in the reference fail the numbers `correct` compares, at a small
size on the CPU, in the set-up waves and in the tail. That the program
agrees with the reference is what every sound run checks
(``test_bench_faults.py``)."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
from tnnbench import harness  # noqa: E402


@pytest.fixture(scope="module")
def readings():
    m = harness.load_manifest()
    c = harness.cell_entry(m, "proto-train-spikes")
    cfg, tr = harness.config_for(m, c), harness.traffic_for(c)
    cfg.update(sites=16, field_side=7)
    tr.update(stream_images=48)
    return control.train_readings(cfg, tr, 2 ** 32 + 3, tail_waves=4)


@pytest.mark.parametrize("case", ["control", "unchanged", "half_batch", "altered"])
def test_control_and_faults_fail_training(readings, case):
    out = readings[case]
    assert set(out) == {"z_mismatch", "w_mismatch", "z_mismatch.tail",
                        "w_mismatch.tail"}
    assert not harness.judge(out)[0], (case, out)
    tail = {k: v for k, v in out.items() if k.endswith(".tail")}
    assert not harness.judge(tail)[0], (case, tail)
