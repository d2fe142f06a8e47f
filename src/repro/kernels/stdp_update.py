"""Pallas TPU kernel: fused STDP weight update.

Fuses four of the paper's macros into one VMEM residency per weight tile:
``stdp_case_gen`` (timing-case planes from x vs z), ``stabilize_func`` (the
weight-indexed BRV probability table — computed as a polynomial-free select
over the <=8 table entries, the vector analogue of the 8-to-1 GDI mux),
``incdec`` (Bernoulli compare -> ±1) and ``syn_weight_update`` (saturating
counter). Random uniforms are passed in explicitly so the kernel is a
deterministic function checked exactly against ref.stdp_ref.

Grid: (columns, synapse tiles, batch tiles). The (Pt, q) inc/dec counters
accumulate across batch tiles in VMEM scratch; the final batch tile applies
the saturating update. Blocks (column-major): x (1, Bt, Pt), z (1, Bt, q),
u (1, Bt, Pt, q) f32, w (1, Pt, q) i32.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def stdp_net_tile(
    w: jax.Array, x: jax.Array, z: jax.Array, uu: jax.Array, ud: jax.Array,
    *,
    T: int,
    w_max: int,
    table: Sequence[float],
    mu_capture: float,
    mu_backoff: float,
    mu_search: float,
) -> jax.Array:
    """One batch tile's pre-clip inc-dec counters: ``stdp_case_gen`` +
    ``stabilize_func`` select chain + ``incdec`` Bernoulli compare.

    w (Pt, q) i32; x (Bt, Pt) i32; z (Bt, q) i32; uu/ud (Bt, Pt, q) f32
    -> (Pt, q) i32. Shared, parity-critical math: this per-layer kernel
    accumulates it across batch tiles, and the fused wave kernel
    (:mod:`repro.kernels.tnn_wave`) runs the same body once per layer
    inside its epilogue — one source keeps every backend bit-identical.
    """
    xs = x[:, :, None]  # (Bt, Pt, 1)
    zs = z[:, None, :]  # (Bt, 1, q)
    x_fired = xs < T
    z_fired = zs < T
    capture = x_fired & z_fired & (xs <= zs)
    backoff = (x_fired & z_fired & (xs > zs)) | (~x_fired & z_fired)
    search = x_fired & ~z_fired

    # stabilize_func: F[w] via select chain over the static table (the mux).
    f = jnp.full(w.shape, table[0], dtype=jnp.float32)
    for wv in range(1, w_max + 1):
        f = jnp.where(w == wv, jnp.float32(table[wv]), f)
    f = f[None, :, :]  # (1, Pt, q)

    p_up = capture * (mu_capture * f) + search * jnp.float32(mu_search)
    p_dn = backoff * (mu_backoff * f)
    inc = (uu < p_up).astype(jnp.int32).sum(axis=0)  # (Pt, q)
    dec = (ud < p_dn).astype(jnp.int32).sum(axis=0)
    return inc - dec


def _stdp_kernel(
    w_ref, x_ref, z_ref, uu_ref, ud_ref, out_ref, net_ref,
    *,
    T: int,
    w_max: int,
    table: Sequence[float],
    mu_capture: float,
    mu_backoff: float,
    mu_search: float,
    n_b_tiles: int,
    out: str,
):
    bt_idx = pl.program_id(2)

    @pl.when(bt_idx == 0)
    def _init():
        net_ref[...] = jnp.zeros_like(net_ref)

    w = w_ref[0].astype(jnp.int32)  # (Pt, q)
    x = x_ref[0].astype(jnp.int32)  # (Bt, Pt)
    z = z_ref[0].astype(jnp.int32)  # (Bt, q)
    net_ref[...] += stdp_net_tile(
        w, x, z, uu_ref[0], ud_ref[0],
        T=T, w_max=w_max, table=table,
        mu_capture=mu_capture, mu_backoff=mu_backoff, mu_search=mu_search)

    @pl.when(bt_idx == n_b_tiles - 1)
    def _apply():
        if out == "net":
            # Pre-clip counter deltas: the form that composes additively
            # across data shards (psum, then one saturating apply).
            out_ref[0] = net_ref[...]
        else:
            out_ref[0] = jnp.clip(w + net_ref[...], 0, w_max)


@functools.partial(
    jax.jit,
    static_argnames=(
        "T", "w_max", "table", "mu_capture", "mu_backoff", "mu_search",
        "block_p", "block_b", "interpret", "out",
    ),
)
def stdp_update_pallas(
    w: jax.Array,
    x: jax.Array,
    z: jax.Array,
    u_up: jax.Array,
    u_dn: jax.Array,
    *,
    T: int = 8,
    w_max: int = 7,
    table: tuple = (),
    mu_capture: float = 10 / 16,
    mu_backoff: float = 6 / 16,
    mu_search: float = 2 / 16,
    block_p: int = 128,
    block_b: int = 128,
    interpret: bool = False,
    out: str = "weights",
) -> jax.Array:
    """w: (C, p, q) ints; x: (C, B, p); z: (C, B, q); u_*: (C, B, p, q) f32
    uniforms — every column of a layer in one column-major launch (the
    column axis leads the grid, blocks are ``(1, rows, lanes)``).

    ``out="weights"`` (default) returns the saturating-updated (C, p, q)
    weights; ``out="net"`` returns the raw batch-summed inc-dec counters
    *before* the clip — the additive form sharded training psums over the
    mesh's "data" axis before one final saturating apply (DESIGN.md §9).
    """
    if out not in ("weights", "net"):
        raise ValueError(f"out={out!r}; one of ('weights', 'net')")
    C, B, p = x.shape
    q = z.shape[2]
    assert w.shape == (C, p, q) and z.shape == (C, B, q)
    assert u_up.shape == (C, B, p, q) and u_dn.shape == (C, B, p, q)
    assert p % block_p == 0 and B % block_b == 0, (p, B, block_p, block_b)
    assert q <= 128
    if not table:
        raise ValueError("pass the stabilization table explicitly")
    n_p, n_b = p // block_p, B // block_b
    kernel = functools.partial(
        _stdp_kernel,
        T=T, w_max=w_max, table=tuple(table),
        mu_capture=mu_capture, mu_backoff=mu_backoff, mu_search=mu_search,
        n_b_tiles=n_b, out=out,
    )
    u_spec = pl.BlockSpec((1, block_b, block_p, q),
                          lambda c, s, b: (c, b, s, 0))
    return pl.pallas_call(
        kernel,
        grid=(C, n_p, n_b),
        in_specs=[
            pl.BlockSpec((1, block_p, q), lambda c, s, b: (c, s, 0)),
            pl.BlockSpec((1, block_b, block_p), lambda c, s, b: (c, b, s)),
            pl.BlockSpec((1, block_b, q), lambda c, s, b: (c, b, 0)),
            u_spec,
            u_spec,
        ],
        out_specs=pl.BlockSpec((1, block_p, q), lambda c, s, b: (c, s, 0)),
        out_shape=jax.ShapeDtypeStruct((C, p, q), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_p, q), jnp.int32)],
        interpret=interpret,
    )(w.astype(jnp.int32), x.astype(jnp.int32), z.astype(jnp.int32),
      u_up.astype(jnp.float32), u_dn.astype(jnp.float32))
