"""Pallas TPU kernel: fused TNN column forward (RNL accumulate + threshold).

This is the silicon's entire datapath — ``syn_output`` ramps feeding the
``pac_adder`` parallel accumulative counter and the threshold comparator —
re-tiled for the TPU memory hierarchy (DESIGN.md §2, §6):

The RNL body potential factors into a 0/1 matmul over the merged
(synapse, ramp-step) axis of size p*T:

    V[b, t, j] = sum_{i,k} [x[b,i] + k <= t] * [k <= w[i,j]]
               = (A @ N)[b*T + t, j]
    A[(b,t), (i,k)] = [x[b,i] + k <= t]      (built on the fly from x)
    N[(i,k), j]     = [k <= w[i,j]]          (built on the fly from w)

so the MXU does the accumulation the pac_adder ripple chain does in silicon
(one bf16 0/1 matmul per ramp step k, see :func:`ramp_matmul`).
Grid: (columns, batch tiles, synapse tiles) with an f32 VMEM accumulator;
on the last synapse tile the crossing time ``z = min{t : V >= theta}`` (and
optionally the WTA mask) is computed in-register and written out.

Block shapes (column-major): x (1, Bt, Pt) int32, w (1, Pt, q) int32, out
(1, Bt, q) int32. Each ramp step's A tile is (Bt*T, Pt) bf16 and its N
tile (Pt, q) bf16 — with the default Bt=64, Pt=256, T=8 that is 256 KiB +
64 KiB per step, well inside v5e VMEM alongside the (Bt*T, q)
accumulator. q stays un-tiled (<= 128 lanes covers every column in the
paper; ops.py pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def ramp_matmul(x: jax.Array, w: jax.Array, *, T: int) -> jax.Array:
    """One tile's RNL body-potential contribution as the §2 A@N matmul.

    x (Bt, Pt) i32 spike times; w (Pt, q) i32 weights -> (Bt*T, q) f32
    partial potentials, row ``b*T + t``. Shared, parity-critical math: the
    per-layer column kernel accumulates these across synapse tiles, the
    fused wave kernel (:mod:`repro.kernels.tnn_wave`) consumes a single
    tile directly — keeping ONE body keeps every backend bit-identical.

    The merged (synapse, ramp-step) contraction is split into one matmul
    per ramp step k: ``A_k[(b,t), i] = [t - x[b,i] >= k]`` against
    ``N_k[i, j] = [w[i,j] >= k]``. Neither operand merges axes into lanes
    (Mosaic refuses that reshape), both are exact 0/1 in bf16, and the f32
    accumulator counts at most Pt*T — exact. Step k = T never contributes
    (``t - x <= T - 1``), so the loop stops at T - 1.
    """
    bt, p_tile = x.shape
    t = jax.lax.broadcasted_iota(jnp.int32, (bt, T, p_tile), 1)
    # elapsed ramp steps at wave position t, one row per (b, t)
    d = (t - x[:, None, :]).reshape(bt * T, p_tile)
    v = jnp.zeros((bt * T, w.shape[1]), jnp.float32)
    for k in range(1, T):
        v += jax.lax.dot_general(
            (d >= k).astype(jnp.bfloat16),
            (w >= k).astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return v


def crossing_wta(V: jax.Array, *, T: int, theta: int, wta: bool) -> jax.Array:
    """Threshold crossing + optional WTA from accumulated potentials.

    V (Bt, T, q) f32 -> spike times (Bt, q) i32: first wave position with
    V >= theta else T; under WTA the earliest spike wins, ties break to the
    lowest index (the paper's systematic tie-break). Shared between the
    column kernel and the fused wave kernel."""
    bt, _, q = V.shape
    crossed = V >= theta
    tt = jax.lax.broadcasted_iota(jnp.int32, (bt, T, q), 1)
    z = jnp.min(jnp.where(crossed, tt, T), axis=1)  # (Bt, q)
    if wta:
        qi = jax.lax.broadcasted_iota(jnp.int32, (bt, q), 1)
        key = z * q + qi  # ties -> lowest index
        winner = jnp.min(key, axis=1, keepdims=True)
        z = jnp.where((key == winner) & (z < T), z, T)
    return z


def _column_kernel(
    x_ref, w_ref, z_ref, acc_ref, *, T: int, theta: int, n_p_tiles: int, wta: bool
):
    pt = pl.program_id(2)

    bt = x_ref.shape[1]
    q = w_ref.shape[2]

    @pl.when(pt == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.int32)  # (Bt, Pt)
    w = w_ref[0].astype(jnp.int32)  # (Pt, q)
    acc_ref[...] += ramp_matmul(x, w, T=T)

    @pl.when(pt == n_p_tiles - 1)
    def _finish():
        z_ref[0] = crossing_wta(
            acc_ref[...].reshape(bt, T, q), T=T, theta=theta, wta=wta)


@functools.partial(
    jax.jit,
    static_argnames=("theta", "T", "wta", "block_b", "block_p", "interpret"),
)
def column_forward_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    theta: int,
    T: int = 8,
    wta: bool = False,
    block_b: int = 64,
    block_p: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x: (C, B, p) int times in [0, T]; w: (C, p, q) int weights.
    Returns (C, B, q) i32 — every column of a layer in one launch.

    Column-major like the fused wave: the column axis is the leading grid
    dimension and each block is ``(1, rows, lanes)``, so the last two block
    dims are the tiled (batch, synapse) extents Mosaic requires. Requires
    B % block_b == 0, p % block_p == 0, q <= 128 (ops.py pads).
    """
    C, B, p = x.shape
    C2, p2, q = w.shape
    assert (C, p) == (C2, p2), (x.shape, w.shape)
    assert B % block_b == 0 and p % block_p == 0, (B, p, block_b, block_p)
    assert q <= 128, "q is kept un-tiled; pad/partition columns beyond 128 neurons"

    n_b, n_p = B // block_b, p // block_p
    kernel = functools.partial(
        _column_kernel, T=T, theta=theta, n_p_tiles=n_p, wta=wta
    )
    return pl.pallas_call(
        kernel,
        grid=(C, n_b, n_p),
        in_specs=[
            pl.BlockSpec((1, block_b, block_p), lambda c, b, s: (c, b, s)),
            pl.BlockSpec((1, block_p, q), lambda c, b, s: (c, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, q), lambda c, b, s: (c, b, 0)),
        out_shape=jax.ShapeDtypeStruct((C, B, q), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b * T, q), jnp.float32)],
        interpret=interpret,
        name="tnn_column_forward",
    )(x.astype(jnp.int32), w.astype(jnp.int32))
