"""The main path's Pallas kernels compile for a described TPU v5e.

Mosaic accepts or refuses a kernel at compile time, and the TPU compiler
is installed even where no chip is attached, so these tests compile the
fused wave and the per-layer forward at the prototype's real widths (625
sites, depth 2, batch 16) with ``interpret=False`` — what the interpreter
used by every other test cannot show. Nothing runs; a pass here is not a
chip run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.tnn_mnist import launcher_network_config
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
