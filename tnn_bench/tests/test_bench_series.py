"""The series cell at a CPU size: the program's encoder and train step
against the plain reference (``tnnbench/reference_series``), whole runs of
the ``train_series`` driver, sound and with the timed path broken, the
comparison's control, and the per-kernel work and roofline readers."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control_series  # noqa: E402
from tnnbench import (common, harness, program, program_series,  # noqa: E402
                      reference_series, series, trace, work_series)
from tnnbench.trace import Event  # noqa: E402

CELL = "skate-train-series"
SEED = 2 ** 33 + 29
Q = 3
_STEPS = {}


def small(L=600, impl="fused"):
    """The cell's configuration and traffic at a CPU size: a column of
    ``L`` synapses (600 pads past the fused wave's 512, 40 fits it) and
    ``Q`` neurons, 8 series a wave from 48, every series firing."""
    m = harness.load_manifest()
    c = harness.cell_entry(m, CELL)
    cfg, tr = harness.config_for(m, c), harness.traffic_for(c)
    cfg.update(series_len=L, widths=[Q], thetas=[L // 4], n_classes=Q)
    cfg["program"]["impl"] = impl
    tr.update(series_len=L, classes=Q, stream_series=48, wave_batch=8)
    return m, cfg, tr


def stream(cfg, n=24, seed=SEED):
    xs, _ = series.series(n, cfg["series_len"], cfg["n_classes"],
                          seed=[seed, 1])
    return xs


@pytest.fixture(scope="module", autouse=True)
def on_the_path():
    program.import_program()


@pytest.mark.parametrize("L", [40, 600])
def test_program_encoder_is_the_reference_bit_for_bit(L):
    _, cfg, _ = small(L)
    xs = stream(cfg)
    xs[0] = 0.5                                   # near the mean: silent
    xs[1, :6] = [1.0, -1.0, 2.75, -3.0, 0.99, 1.25]   # on and around levels
    xs[1, 6:] = np.linspace(-3.0, 3.0, L - 6)
    net = program_series.network(cfg)
    x = program_series.encode(xs, net)
    ref = reference_series.encode(xs, cfg)
    assert x.shape == ref.shape == (24, 1, L)
    np.testing.assert_array_equal(x, ref)
    assert (ref[0] == 8).all()
    assert ref[1, 0, :6].tolist() == [7, 7, 0, 0, 8, 6]


@pytest.mark.parametrize("impl", ["fused", "direct"])
@pytest.mark.parametrize("L", [40, 600])
def test_train_step_is_the_reference(L, impl):
    """Three learning waves through the program's step (the fused wave at
    40 synapses, the per-layer kernels at 600, and ``impl="direct"``) give
    the reference's winner times and weights."""
    import jax

    _, cfg, _ = small(L, impl)
    x = reference_series.encode(stream(cfg), cfg)
    w0 = [np.asarray(w) for w in reference_series.make_weights(cfg, SEED)]
    xs = [common.rows(x, 8, w) for w in range(3)]
    key = common.stdp_key(SEED)
    ref_z, ref_w = reference_series.train(w0, xs, key, cfg)
    step = program.train_step(program_series.network(cfg))
    state = program.train_state([jax.numpy.asarray(w) for w in w0],
                                common.stdp_key(SEED))
    for xb, rz in zip(xs, ref_z):
        state, z = step(state, jax.numpy.asarray(xb))
        np.testing.assert_array_equal(np.asarray(z), rz)
    np.testing.assert_array_equal(program.weights(state)[0], ref_w[0])


@pytest.fixture
def one_compile_per_network(monkeypatch):
    make = program.train_step

    def train_step(net):
        if net not in _STEPS:
            _STEPS[net] = make(net)
        return _STEPS[net]

    monkeypatch.setattr(program, "train_step", train_step)


def run():
    m, cfg, tr = small(impl="direct")
    out = harness.run_cell(CELL, SEED, 0.3, False, time.perf_counter(),
                           manifest=m, cfg=cfg, traffic=tr,
                           require_chip=False)
    assert out["attempted"] > 0
    return out


def _wrap_step(monkeypatch, wrap):
    make = program.train_step
    monkeypatch.setattr(program, "train_step", lambda net: wrap(make(net), net))


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
    """Sound through the set-up waves (which also compile the forward that
    stands in after them); the state stops updating in the window."""
    def wrap(step, net):
        fwd, calls = _forward(net), [0]

        def broken(st, x):
            calls[0] += 1
            if calls[0] > 3:
                return st, fwd(st, x)
            fwd(st, x)
            return step(st, x)
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


def encoder_bf16(monkeypatch):
    """The program's encoder fed series rounded to bfloat16: a single
    reduced-precision pass on the way in."""
    import jax.numpy as jnp

    encode = program_series.encode
    monkeypatch.setattr(program_series, "encode", lambda xs, net: encode(
        np.asarray(jnp.asarray(xs, jnp.bfloat16).astype(jnp.float32)), net))


def test_sound_run_is_correct(one_compile_per_network):
    out = run()
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"x_mismatch", "z_mismatch", "w_mismatch",
                                  "z_mismatch.tail", "w_mismatch.tail"}
    assert out["metrics"]["train_img_s"]["value"] > 0


@pytest.mark.parametrize("fault", [unchanged_state, unchanged_after_setup,
                                   half_batch, altered_z, encoder_bf16])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch,
                                            one_compile_per_network):
    fault(monkeypatch)
    out = run()
    assert not out["correct"], out["checks"]
    if fault is encoder_bf16:
        assert out["checks"]["x_mismatch"]["value"] > 0
    else:
        tail = [c["value"] for k, c in out["checks"].items()
                if k.endswith(".tail")]
        assert any(tail), out["checks"]


def test_a_series_of_another_shape_is_refused():
    m, cfg, tr = small()
    tr.update(series_len=601)
    with pytest.raises(ValueError, match="differ"):
        harness.run_cell(CELL, SEED, 0.1, False, time.perf_counter(),
                         manifest=m, cfg=cfg, traffic=tr, require_chip=False)
    cfg.update(widths=[Q + 1])
    with pytest.raises(ValueError, match="configuration states"):
        program_series.network(cfg)


@pytest.fixture(scope="module")
def readings():
    _, cfg, tr = small()
    return control_series.series_readings(cfg, tr, SEED, tail_waves=4)


@pytest.mark.parametrize("case", control_series.CASES)
def test_control_and_faults_fail(readings, case):
    """The control (the STDP compares in bfloat16) and each fault fail; a
    fault of the step fails the tail on its own."""
    out = readings[case]
    assert not harness.judge(out)[0], (case, out)
    if case not in ("control", "encoder_bf16"):
        tail = {k: v for k, v in out.items() if k.endswith(".tail")}
        assert not harness.judge(tail)[0], (case, tail)


def test_work_at_the_published_shape():
    cfg = harness.config_for(harness.load_manifest(),
                             harness.cell_entry(harness.load_manifest(), CELL))
    assert work_series.column(cfg) == (1882, 7)
    fwd, stdp = work_series.forward(cfg, 128), work_series.stdp(cfg, 128)
    assert fwd.ops == 2 * 13_174 * 8 * 128
    assert fwd.bytes == 128 * 1882 + 13_174 + 128 * 7
    assert stdp.ops == 3 * 13_174 * 128
    assert stdp.bytes == 128 * (1882 + 7) + 2 * 13_174


def test_kernel_roofline_reads_its_kernel_by_name():
    ops = [Event("tnn_column_forward.1", 100, 100, "x tpu_custom_call"),
           Event("tnn_stdp_update.1", 200, 400, "x tpu_custom_call"),
           Event("copy.3", 600, 300, "copy"),
           Event("tnn_column_forward.1", 1100, 100, "x tpu_custom_call"),
           Event("tnn_stdp_update.1", 1200, 400, "x tpu_custom_call")]
    view = trace.TraceView({"/device:TPU:0": ops},
                           [Event("bench.window", 0, 2000)])

    class Run:
        counters = {"waves": 2, "tnn_column_forward.ops": 2e3,
                    "tnn_column_forward.bytes": 1.0,
                    "tnn_stdp_update.ops": 1e3,
                    "tnn_stdp_update.bytes": 40.0}
        peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}

    Run.view = view
    # forward: least 2 ns a launch (ops), 2 launches over 200 ns
    assert harness.reader("column_roofline.skate")(Run) == pytest.approx(2.0)
    # STDP: least 40 ns a launch (bytes), 2 launches over 800 ns
    assert harness.reader("stdp_roofline.skate")(Run) == pytest.approx(10.0)
    Run.view = trace.TraceView({"/device:TPU:0": ops[2:3]},
                               [Event("bench.window", 0, 2000)])
    assert harness.reader("column_roofline.skate")(Run) is None
    Run.view = None
    assert harness.reader("stdp_roofline.skate")(Run) is None
