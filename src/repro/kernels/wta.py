"""Pallas TPU kernel: WTA lateral inhibition (`less_equal` macro semantics).

The silicon's pass-transistor less_equal chain sequentially kills every
neuron that sees an earlier-or-equal spike at a lower index. On TPU this is
a 2-reduction: minimize the fused key ``z*q + index`` (so ties break to the
lowest index exactly as the paper's systematic tie-break), then null every
non-winner to T. One grid dim over batch tiles; the neuron axis lives in
lanes (q <= 128, padded by ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wta_kernel(z_ref, out_ref, *, T: int):
    z = z_ref[...].astype(jnp.int32)  # (Bt, q)
    bt, q = z.shape
    qi = jax.lax.broadcasted_iota(jnp.int32, (bt, q), 1)
    key = z * q + qi
    winner = jnp.min(key, axis=1, keepdims=True)
    out_ref[...] = jnp.where((key == winner) & (z < T), z, T)


@functools.partial(jax.jit, static_argnames=("T", "block_b", "interpret"))
def wta_pallas(
    z: jax.Array, *, T: int = 8, block_b: int = 128, interpret: bool = False
) -> jax.Array:
    """z: (B, q) spike times -> post-inhibition times (B, q) int32."""
    B, q = z.shape
    assert B % block_b == 0, (B, block_b)
    assert q <= 128
    return pl.pallas_call(
        functools.partial(_wta_kernel, T=T),
        grid=(B // block_b,),
        in_specs=[pl.BlockSpec((block_b, q), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((block_b, q), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, q), jnp.int32),
        interpret=interpret,
        name="tnn_wta",
    )(z.astype(jnp.int32))
