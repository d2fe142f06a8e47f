"""The four-chip cell's control and faults, planted in the reference, fail
the numbers `correct` compares, at a small size on the CPU, in the set-up
waves and in the tail; among them the two faults only a data mesh can
have. That the sharded program agrees with the reference is what every
sound run checks (``test_bench_mesh.py``)."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import control_mesh  # noqa: E402
from tnnbench import common, harness, reference, seeds  # noqa: E402

SEED = 2 ** 32 + 29


@pytest.fixture(scope="module")
def small():
    m = harness.load_manifest()
    c = harness.cell_entry(m, "proto-train-dp4")
    cfg, tr = harness.config_for(m, c), harness.traffic_for(c)
    cfg.update(sites=16, field_side=7)
    tr.update(stream_images=64)
    return cfg, tr


@pytest.fixture(scope="module")
def readings(small):
    cfg, tr = small
    out = control.train_readings(cfg, tr, SEED, tail_waves=4)
    out.update(control_mesh.mesh_readings(cfg, tr, SEED, tail_waves=4))
    return out


@pytest.mark.parametrize("case", ["control", "unchanged", "half_batch",
                                  "altered", "shard_rows_left_out",
                                  "no_all_reduce"])
def test_control_and_faults_fail_mesh_training(readings, case):
    out = readings[case]
    assert set(out) == {"z_mismatch", "w_mismatch", "z_mismatch.tail",
                        "w_mismatch.tail"}
    assert not harness.judge(out)[0], (case, out)
    tail = {k: v for k, v in out.items() if k.endswith(".tail")}
    assert not harness.judge(tail)[0], (case, tail)


def test_one_shard_is_the_reference(small):
    """With one shard, the no-all-reduce fault is no fault: the fault's
    reading follows the reference bit for bit."""
    cfg, tr = small
    B = int(tr["wave_batch"])
    x = reference.encode(common.frames(cfg, B * 2, SEED, seeds.TRAIN_IMAGES), cfg)
    w0 = [np.asarray(w) for w in common.make_weights(cfg, SEED)]
    xs = [common.rows(x, B, w) for w in range(2)]
    key = common.stdp_key(SEED)
    want = reference.train(w0, xs, key, cfg)
    got = control_mesh.shard_train(w0, xs, key, cfg, 1)
    for a, b in zip(got[0] + got[1], want[0] + want[1], strict=True):
        np.testing.assert_array_equal(a, b)
