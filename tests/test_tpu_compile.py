"""The main path's Pallas kernels compile for a described TPU v5e.

Mosaic accepts or refuses a kernel at compile time, and the TPU compiler
is installed even where no chip is attached, so these tests compile the
fused wave, the per-layer forward and the whole train step at the
prototype's real widths (625 sites, depth 2, batch 16), and the
time-series column's train step at its published shape (one site, 1,882
synapses, 7 neurons, batch 128), with ``interpret=False`` — what the
interpreter used by every other test cannot show. Nothing runs; a pass
here is not a chip run.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import tnn_ucr
from repro.configs.tnn_mnist import launcher_network_config
from repro.core.network import init_train_state, make_train_step
from repro.kernels import ops, padding, tnn_wave

SITES, B = 625, 16


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile cache
    off: an entry compiled for a described chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel, not the interpreter
    return text


def _prototype(one_chip):
    cfg = launcher_network_config(SITES, depth=2, impl="fused")
    plan = padding.network_plan(cfg, B, interpret=False)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = spec((B, SITES, cfg.layers[0].column.p), jnp.uint8)
    ws = tuple(spec((SITES, l.column.p, l.column.q), jnp.int8)
               for l in cfg.layers)
    us = tuple((spec((SITES, B, l.column.p, l.column.q), jnp.float32),) * 2
               for l in cfg.layers)
    return plan, x, ws, us


def test_wave_forward_compiles_for_v5e(one_chip):
    plan, x, ws, _ = _prototype(one_chip)
    _compile(lambda x, ws: tnn_wave.wave_forward(x, ws, plan=plan), x, ws)


def test_wave_train_compiles_for_v5e(one_chip):
    plan, x, ws, us = _prototype(one_chip)
    _compile(lambda x, ws, us: tnn_wave.wave_train(x, ws, us, plan=plan),
             x, ws, us)


def test_layer_forward_fused_compiles_for_v5e(one_chip):
    _, x, ws, _ = _prototype(one_chip)
    _compile(lambda x, w: ops.layer_forward_fused(
        x, w, theta=24, T=8, interpret=False), x, ws[0])


def test_train_step_draws_uniforms_on_dense_lanes(one_chip, monkeypatch):
    """The STDP uniforms are drawn per site over the flattened synapse axis
    and kept so: a (p, q)-minor draw fills 12 or 10 of a tile's 128 lanes,
    which made threefry hash about twelve times the elements it needs."""
    monkeypatch.setattr(padding, "resolve_interpret",
                        lambda interpret=None: bool(interpret))
    cfg = launcher_network_config(SITES, depth=2, impl="fused")
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg)))
    x = jax.ShapeDtypeStruct((B, SITES, cfg.layers[0].column.p), jnp.uint8,
                             sharding=one_chip)
    text = make_train_step(cfg).lower(state, x).compile().as_text()
    assert "tpu_custom_call" in text
    fusions = []                          # (output shape, op_name)
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) fusion\(", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            fusions.append((m.group(1), op.group(1) if op else ""))
    draws = {re.sub(r"\{.*", "", shape) for shape, op in fusions
             if shape.startswith("f32[") and "tnn.uniforms" in op
             and "_uniform" in op}
    assert draws == {f"f32[{SITES},2,{B},384]", f"f32[{SITES},2,{B},120]"}
    folded = f"f32[{SITES},2,{B},32,12]"
    assert not [shape for shape, _ in fusions if folded in shape]


def test_series_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """The published time-series column runs the per-layer kernels (padded
    p 1,888 is past the fused wave's cap). At the wrappers' default
    blocks the STDP kernel's uniforms blocks take 32 MiB of VMEM, over
    v5e's 16 MiB scoped limit: the blocks come from the shape."""
    monkeypatch.setattr(padding, "resolve_interpret",
                        lambda interpret=None: bool(interpret))
    cfg = tnn_ucr.network_config()
    assert not padding.fused_wave_capable(cfg)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg)))
    x = jax.ShapeDtypeStruct((128, 1, 1882), jnp.uint8, sharding=one_chip)
    text = make_train_step(cfg).lower(state, x).compile().as_text()
    kernels = re.findall(r"%(tnn_\w+)\.\d+ = \S+ custom-call\(", text)
    assert sorted(kernels) == ["tnn_column_forward", "tnn_stdp_update"]
    assert ops.stdp_block_b(1882, 7, block_b=128, block_p=128) == 32
    # the prototype's layers keep the wrappers' blocks at any batch to 128
    for p, q in ((32, 12), (12, 10)):
        assert ops.stdp_block_b(p, q, block_b=128, block_p=128) == 128
