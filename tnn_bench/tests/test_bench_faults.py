"""A whole run, with the chip look skipped, at a small size on the CPU: sound,
`correct` is true; with the timed path broken underneath, it is false, once
for each fault a one-chip training cell can have."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from tnnbench import harness, program  # noqa: E402

SEED = 2 ** 33 + 11
CELL = "proto-train-spikes"
_STEPS = {}


@pytest.fixture(autouse=True)
def one_compile_per_network(monkeypatch):
    """Every run here builds the step anew; let them share one jitted step
    per network, so that it compiles once in this module."""
    make = program.train_step

    def train_step(net):
        if net not in _STEPS:
            _STEPS[net] = make(net)
        return _STEPS[net]

    monkeypatch.setattr(program, "train_step", train_step)


def run(input="spikes"):
    m = harness.load_manifest()
    c = harness.cell_entry(m, CELL)
    cfg, tr = harness.config_for(m, c), harness.traffic_for(c)
    cfg.update(sites=16, field_side=7)
    cfg["program"]["impl"] = "direct"
    tr.update(stream_images=64, input=input)
    out = harness.run_cell(CELL, SEED, 0.3, False, time.perf_counter(),
                           manifest=m, cfg=cfg, traffic=tr,
                           require_chip=False)
    assert out["attempted"] > 0
    return out


def _wrap_step(monkeypatch, wrap):
    make = program.train_step

    def train_step(net):
        return wrap(make(net), net)

    monkeypatch.setattr(program, "train_step", train_step)


def _forward(net):
    import jax
    from repro.core.network import network_forward, params_from_tree

    return jax.jit(lambda st, x: network_forward(
        x, params_from_tree(st["params"], net), net)[-1])


def unchanged_state(monkeypatch):
    def wrap(step, net):
        fwd = _forward(net)
        return lambda st, x: (st, fwd(st, x))

    _wrap_step(monkeypatch, wrap)


def unchanged_after_setup(monkeypatch):
    """Sound through the set-up waves; the state stops updating after."""
    def wrap(step, net):
        fwd, calls = _forward(net), [0]

        def broken(st, x):
            calls[0] += 1
            return step(st, x) if calls[0] <= 3 else (st, fwd(st, x))
        return broken

    _wrap_step(monkeypatch, wrap)


def half_batch(monkeypatch):
    def wrap(step, net):
        return lambda st, x: step(st, x.at[x.shape[0] // 2:].set(8))

    _wrap_step(monkeypatch, wrap)


def altered_z(monkeypatch):
    def wrap(step, net):
        def broken(st, x):
            st, z = step(st, x)
            return st, z.at[0, 0, 0].set((z[0, 0, 0] + 1) % 9)
        return broken

    _wrap_step(monkeypatch, wrap)


@pytest.mark.parametrize("input", ["spikes", "frames"])
def test_sound_run_is_correct(input):
    out = run(input)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["failed"] == 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert ("x_mismatch" in out["checks"]) == (input == "frames")


@pytest.mark.parametrize("fault", [unchanged_state, unchanged_after_setup,
                                   half_batch, altered_z])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = run()
    assert not out["correct"], out["checks"]
    tail = {k: c["value"] for k, c in out["checks"].items() if k.endswith(".tail")}
    assert any(tail.values()), out["checks"]


def test_no_tpu_exits_before_measuring():
    with pytest.raises(SystemExit):
        harness.require_chips(1)
