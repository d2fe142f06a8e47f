"""The time-series clustering column (``configs/tnn_ucr.py``) through the
program's normal path at a CPU size: the series front end on the network
config, the kernel launches of a learning wave, an epoch of
``TNNTrainer`` with checkpoint and resume, ``TNNEngine`` serving the
config, and one compile across waves. Exactness against the benchmark's
plain reference is checked in ``tnn_bench/tests/test_bench_series.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, restore_tnn
from repro.configs import tnn_ucr
from repro.core.network import (
    encode,
    encode_series,
    init_train_state,
    input_wave_spec,
    make_train_step,
    network_train_step,
    params_from_tree,
)
from repro.data.series import series
from repro.serve.tnn_engine import ClassifyRequest, TNNEngine
from repro.train.tnn_trainer import TNNTrainConfig, TNNTrainer
from repro.utils import tracing

WIDE, NARROW, Q = 600, 40, 3   # 600 pads past the fused wave's 512


def _cfg(L=WIDE, impl="fused"):
    return tnn_ucr.network_config(series_len=L, clusters=Q, theta=L // 4,
                                  impl=impl)


def test_published_shape():
    cfg = tnn_ucr.network_config()
    (layer,) = cfg.layers
    assert (layer.n_cols, layer.column.p, layer.column.q) == (1, 1882, 7)
    assert cfg.n_synapses == 13_174 and cfg.n_classes == 7
    assert cfg.series_len == 1882 and layer.column.impl == "fused"
    assert layer.column.theta == 400 and layer.column.stdp.mu_search == 1 / 16


def test_front_end_is_validated_against_the_column():
    cfg = _cfg()
    assert input_wave_spec(cfg) == cfg.layers[0].column.wave
    with pytest.raises(ValueError, match="series front end"):
        input_wave_spec(dataclasses.replace(cfg, series_len=WIDE + 1))
    wide = dataclasses.replace(cfg, layers=(dataclasses.replace(
        cfg.layers[0], n_cols=2),))
    with pytest.raises(ValueError, match="series front end"):
        input_wave_spec(wide)


def test_series_encoding():
    """A sample spikes the earlier the further it lies from the mean, above
    or below: |x| >= 2.75 at tick 0, one tick later for each quarter of a
    standard deviation nearer, |x| < 1 never; ``encode`` picks this
    encoder."""
    cfg = _cfg(NARROW)
    T = cfg.layers[0].column.wave.T
    x, _ = series(6, NARROW, Q, seed=4)
    x[5, :8] = [2.75, -2.75, 2.74, 1.0, -1.0, 0.99, 0.0, -9.0]
    t = np.asarray(encode_series(jnp.asarray(x), cfg))
    assert t.shape == (6, 1, NARROW) and t.dtype == np.uint8
    assert t[5, 0, :8].tolist() == [0, 0, 1, T - 1, T - 1, T, T, 0]
    np.testing.assert_array_equal(t[:, 0] == T, np.abs(x) < 1.0)
    np.testing.assert_array_equal(t, np.asarray(encode_series(
        jnp.asarray(-x), cfg)))
    np.testing.assert_array_equal(np.asarray(encode(jnp.asarray(x), cfg)), t)


@pytest.mark.parametrize("L,launches", [(WIDE, 2), (NARROW, 1)])
def test_pallas_launches_per_learning_wave(L, launches):
    """Past ``MAX_FUSED_P1`` a wave is the per-layer column forward and STDP
    kernels; a narrow series still fits the one fused wave kernel."""
    cfg = _cfg(L)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    params = params_from_tree(state["params"], cfg)
    x = jnp.zeros((8, 1, L), jnp.uint8)
    n = tracing.pallas_launch_count(
        lambda x, k: network_train_step(x, params, cfg, k)[1][0], x,
        jax.random.PRNGKey(1))
    assert n == launches


def test_series_step_compiles_once():
    cfg = _cfg()
    step = make_train_step(cfg)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    x = encode(jnp.asarray(series(8, WIDE, Q, seed=2)[0]), cfg)
    state, z = step(state, x)
    jax.block_until_ready(z)
    n = tracing.compile_count()
    for _ in range(3):
        state, z = step(state, x)
    jax.block_until_ready(z)
    assert tracing.compile_count() == n and int(state["wave"]) == 4


def _tcfg(path, epochs):
    return TNNTrainConfig(epochs=epochs, wave_batch=8, train_size=16,
                          eval_size=8, ckpt_dir=str(path), log_every=1000)


def test_trainer_resumes_and_engine_serves(tmp_path):
    """One epoch, saved, resumed for a second equals two straight through;
    the engine warm-started from the checkpoint serves series, as one fit
    on the trainer's stream does."""
    cfg = _cfg()
    out_a = TNNTrainer(cfg, _tcfg(tmp_path / "a", 2)).run()
    TNNTrainer(cfg, _tcfg(tmp_path / "b", 1)).run()
    tr = TNNTrainer(cfg, _tcfg(tmp_path / "b", 2))
    out_b = tr.run()
    assert out_b["resumed"] and out_a["final_wave"] == out_b["final_wave"] == 4
    sa, _ = restore_tnn(Checkpointer(str(tmp_path / "a")), cfg)
    sb, extra = restore_tnn(Checkpointer(str(tmp_path / "b")), cfg)
    for k in ("rng", "wave", "vote_table"):
        np.testing.assert_array_equal(np.asarray(sa[k]), np.asarray(sb[k]))
    np.testing.assert_array_equal(np.asarray(sa["params"]["layer_00"]),
                                  np.asarray(sb["params"]["layer_00"]))
    assert extra["has_vote"] and out_a["accuracy"] == out_b["accuracy"]

    warm = TNNEngine.from_checkpoint(str(tmp_path / "b"), cfg, n_slots=4)
    fit = TNNEngine(cfg, params_from_tree(sb["params"], cfg), n_slots=4)
    fit.fit(tr.stream.images, tr.stream.labels)
    x, _ = series(6, WIDE, Q, seed=9)
    for eng in (warm, fit):
        for uid in range(6):
            eng.submit(ClassifyRequest(uid=uid, image=x[uid]))
        eng.run_until_done()
    got = [warm.done[u].result for u in range(6)]
    assert got == [fit.done[u].result for u in range(6)]
    assert all(0 <= int(c) < Q for c in got)
