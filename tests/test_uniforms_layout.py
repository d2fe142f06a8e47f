"""``layer_uniforms`` draws each site's STDP uniforms over its flattened
synapse axis, ``(2, B, p*q)``, and reshapes after: the bits must be those
of drawing ``(2, B, p, q)`` per column, under either threefry counter
scheme, or the fused backends would stop matching the reference path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.column import ColumnConfig
from repro.core.layer import LayerConfig, layer_uniforms

N_COLS = 7


@pytest.mark.parametrize("partitionable", [True, False])
@pytest.mark.parametrize("p,q", [(32, 12), (12, 10)])  # the prototype's layers
@pytest.mark.parametrize("B", [16, 5])
def test_layer_uniforms_bits_match_per_column_draw(partitionable, p, q, B):
    cfg = LayerConfig(n_cols=N_COLS,
                      column=ColumnConfig(p=p, q=q, theta=1, impl="fused"))
    key = jax.random.PRNGKey(20_121_005)
    with jax.threefry_partitionable(partitionable):
        got = jax.jit(layer_uniforms, static_argnums=(1, 2))(key, cfg, B)
        want = jax.vmap(
            lambda k: jax.random.uniform(k, (2, B, p, q), dtype=jnp.float32)
        )(jax.random.split(key, N_COLS))
    assert got.shape == (N_COLS, 2, B, p, q) and got.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))
