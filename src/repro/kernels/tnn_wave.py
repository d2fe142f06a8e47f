"""Pallas TPU megakernel: one launch per gamma wave for the whole network.

The paper's 7nm prototype processes a gamma wave as a single hardware
pipeline — each layer's spike volley flows straight into the next layer's
columns without ever leaving the datapath. This kernel is the software
analog (DESIGN.md §10, §11): for each (column site, batch tile) grid cell
it runs the whole N-layer cascade

    layer-1 RNL accumulate + threshold + WTA        (the §2 A@N matmul)
      -> inter-layer spike volley, held in VMEM/registers
    layer-2 RNL accumulate + threshold + WTA
      -> ... layer-N RNL accumulate + threshold + WTA
      -> optional STDP-counter epilogue for EVERY layer

so no intermediate ``(B, S, q_i)`` volley ever round-trips through HBM and
the per-layer kernel chain (N forward + N STDP ``pallas_call`` launches per
wave) collapses to ONE launch at any depth. Same-site topology makes this
embarrassingly column-parallel: site s of layer i+1 reads only site s of
layer i, so the column axis is the leading grid dimension and no cross-site
traffic exists.

Grid: ``(n_cols, batch tiles)``; batch is the minor (sequential) dimension,
so the per-column STDP counter scratch accumulates across batch tiles and
the final tile emits the pre-clip ``out="net"`` counters — the additive
form sharded training psums over the mesh's "data" axis before one
saturating apply, exactly like the per-layer path (DESIGN.md §9).

Layout: arrays arrive column-major — x ``(C, Bp, p1p)``, weights
``(C, p_i, q_i)``, uniforms ``(C, Bp, p_i, q_i)`` — matching the per-column
RNG split the reference path draws, so the Bernoulli compares see identical
bits and the whole wave is bit-exact with ``impl="direct"``.

Geometry comes from a precomputed :class:`repro.kernels.padding.NetworkPlan`
(static, hashable, lru-cached per config): the layer-1 synapse axis lives in
a single tile (padded p1 <= ``MAX_FUSED_P1``), every q_i stays un-tiled in
lanes (<= 128) — which also bounds every deeper fan-in, since
``p_{i+1} = q_i`` — and padding follows the package's no-op encodings
(spikes=T, weights=0, uniforms=1.0). The per-layer loop below is a Python
loop over the plan's static tuples, so the cascade unrolls at trace time:
depth costs trace size, never launch count.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.padding import NetworkPlan
from repro.kernels.stdp_update import stdp_net_tile
from repro.kernels.tnn_column import crossing_wta, ramp_matmul
from repro.utils import tracing


def _rnl_wta(x: jax.Array, w: jax.Array, *, T: int, theta: int) -> jax.Array:
    """One layer's forward for one (column, batch-tile) cell: the §2 A@N
    0/1 matmul, threshold crossing, and WTA — x (Bt, P) i32, w (P, q) i32
    -> post-WTA spike times (Bt, q) i32. The parity-critical math is the
    SAME ``ramp_matmul``/``crossing_wta`` bodies the per-layer column
    kernel runs; here the synapse axis is a single tile (the plan
    guarantees P fits), so no cross-tile accumulator is needed."""
    bt = x.shape[0]
    q = w.shape[1]
    v = ramp_matmul(x, w, T=T).reshape(bt, T, q)
    return crossing_wta(v, T=T, theta=theta, wta=True)


def _wave_kernel(
    x_ref, *refs,
    T: int, thetas: Tuple[int, ...], n_b_tiles: int, learn: bool,
    w_max: int, tables, mus,
):
    """The whole N-layer wave for one (column, batch-tile) grid cell.

    ``refs`` layout (n = len(thetas) layers): n weight refs; then, when
    learning, 2n uniform refs (up/dn interleaved per layer); then n z
    output refs; then, when learning, n net output refs and n VMEM counter
    scratch accumulators. The layer loop is unrolled at trace time from the
    plan's static per-layer tuples."""
    n = len(thetas)
    w_refs, rest = refs[:n], refs[n:]
    if learn:
        u_refs, rest = rest[:2 * n], rest[2 * n:]
        z_refs, net_refs, net_accs = rest[:n], rest[n:2 * n], rest[2 * n:]
        bt_idx = pl.program_id(1)

        @pl.when(bt_idx == 0)
        def _init():
            for acc in net_accs:
                acc[...] = jnp.zeros_like(acc)
    else:
        z_refs = rest

    # the whole wave, volleys in registers/VMEM: no HBM round-trip between
    # layers, no re-padding between stages. Widening to the i32 accumulator
    # happens HERE, inside the kernel — under a packed plan the refs hold
    # uint8 volleys / int8 weights and these casts are the only widening
    # the wave ever does (DESIGN.md §14).
    v = x_ref[0].astype(jnp.int32)        # (Bt, p1p)
    for i in range(n):
        w = w_refs[i][0].astype(jnp.int32)  # (p_i, q_i)
        z = _rnl_wta(v, w, T=T, theta=thetas[i])  # (Bt, q_i)
        z_refs[i][0] = z.astype(z_refs[i].dtype)
        if learn:
            net_accs[i][...] += stdp_net_tile(
                w, v, z, u_refs[2 * i][0], u_refs[2 * i + 1][0],
                T=T, w_max=w_max, table=tables[i],
                mu_capture=mus[i][0], mu_backoff=mus[i][1],
                mu_search=mus[i][2])
        v = z

    if learn:
        @pl.when(bt_idx == n_b_tiles - 1)
        def _emit():
            for net_ref, acc in zip(net_refs, net_accs):
                net_ref[0] = acc[...]


def _wave_pallas_call(plan: NetworkPlan, learn: bool):
    """Build the single-launch pallas_call for one gamma wave under ``plan``."""
    C, bt = plan.n_cols, plan.pad.block_b
    bp, n_b = plan.pad.bp, plan.pad.n_b
    pps, qs = plan.pps, plan.qs
    in_specs = [pl.BlockSpec((1, bt, pps[0]), lambda c, b: (c, b, 0))]  # x
    for pp, q in zip(pps, qs):  # per-layer weights
        in_specs.append(pl.BlockSpec((1, pp, q), lambda c, b: (c, 0, 0)))
    out_specs = [pl.BlockSpec((1, bt, q), lambda c, b: (c, b, 0))
                 for q in qs]  # per-layer z
    z_dtype = jnp.uint8 if plan.packed else jnp.int32
    out_shape = [jax.ShapeDtypeStruct((C, bp, q), z_dtype) for q in qs]
    scratch = []
    if learn:
        for pp, q in zip(pps, qs):  # per-layer up/dn uniforms
            u_spec = pl.BlockSpec((1, bt, pp, q), lambda c, b: (c, b, 0, 0))
            in_specs += [u_spec, u_spec]
        out_specs += [pl.BlockSpec((1, pp, q), lambda c, b: (c, 0, 0))
                      for pp, q in zip(pps, qs)]  # per-layer net counters
        out_shape += [jax.ShapeDtypeStruct((C, pp, q), jnp.int32)
                      for pp, q in zip(pps, qs)]
        scratch = [pltpu.VMEM((pp, q), jnp.int32) for pp, q in zip(pps, qs)]
    kernel = functools.partial(
        _wave_kernel,
        T=plan.T, thetas=plan.thetas,
        n_b_tiles=n_b, learn=learn, w_max=plan.w_max,
        tables=plan.tables, mus=plan.mus,
    )
    return pl.pallas_call(
        kernel,
        grid=(C, n_b),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=plan.pad.interpret,
        name="tnn_wave_train" if learn else "tnn_wave_forward",
    )


def _prep_inputs(x, params, plan: NetworkPlan):
    """Apply the plan's no-op pad encodings once and go column-major. Only
    the input-facing synapse axis needs padding; deeper weights already
    match the in-VMEM volley extents.

    Dtype contract (DESIGN.md §14): under a packed plan the volley crosses
    the launch boundary as uint8 and the weights as int8 — 1/4 the
    HBM/VMEM bytes — and the kernel body widens to its i32 accumulator
    internally. An unpacked plan widens everything to i32 here, before the
    launch. Both layouts compile for a v5e at the prototype's widths
    (``tests/test_tpu_compile.py`` covers the packed one)."""
    pad = plan.pad
    x_dt = jnp.uint8 if plan.packed else jnp.int32
    w_dt = jnp.int8 if plan.packed else jnp.int32
    x = pad.pad_spikes(x, plan.T, b_axis=0, p_axis=2)       # (Bp, C, p1p)
    xT = x.transpose(1, 0, 2).astype(x_dt)                  # (C, Bp, p1p)
    ws = [pad.pad_weights(params[0], p_axis=1).astype(w_dt)]
    ws += [w.astype(w_dt) for w in params[1:]]
    return [xT] + ws


@functools.partial(jax.jit, static_argnames=("plan",))
def wave_forward(
    x: jax.Array, params: Tuple[jax.Array, ...], *, plan: NetworkPlan
) -> Tuple[jax.Array, ...]:
    """One fused forward gamma wave through the whole cascade. x (B, C, p1)
    ints; params = per-layer weights (w_i (C, p_i, q_i)). Returns the
    per-layer post-WTA spike times (z_i (B, C, q_i)) — uint8 under a
    packed plan, i32 otherwise; identical bits either way, and bit-exact
    with the per-layer backends at any depth."""
    with jax.named_scope(tracing.WAVE):
        zs = _wave_pallas_call(plan, learn=False)(
            *_prep_inputs(x, params, plan))
        B = plan.pad.b
        return tuple(z.transpose(1, 0, 2)[:B] for z in zs)


@functools.partial(jax.jit, static_argnames=("plan",))
def wave_train(
    x: jax.Array,
    params: Tuple[jax.Array, ...],
    uniforms: Tuple[Tuple[jax.Array, jax.Array], ...],
    *,
    plan: NetworkPlan,
) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    """One fused learning gamma wave: forward through every layer PLUS the
    per-layer STDP-counter epilogue, one launch at any depth.

    uniforms: per-layer ``(u_up, u_dn)`` pairs, each (C, B, p_i, q_i) — the
    same draws (same per-layer/per-column key split) the reference path
    makes, passed in explicitly so the update is a deterministic,
    oracle-checkable function. Returns ``(zs, nets)``: per-layer post-WTA
    spike times and the PRE-CLIP batch-summed counter deltas (``out="net"``
    semantics, DESIGN.md §9) — deltas from disjoint batch shards sum (psum)
    before one saturating ``apply_net``, so sharded training stays
    bit-identical."""
    pad = plan.pad
    with jax.named_scope(tracing.WAVE):
        inputs = _prep_inputs(x, params, plan)
        for i, (uu, ud) in enumerate(uniforms):
            p_axis = 2 if i == 0 else None  # only layer 1's fan-in is padded
            inputs.append(pad.pad_uniforms(uu, b_axis=1, p_axis=p_axis))
            inputs.append(pad.pad_uniforms(ud, b_axis=1, p_axis=p_axis))
        outs = _wave_pallas_call(plan, learn=True)(*inputs)
        n = plan.n_layers
        zs, nets = outs[:n], outs[n:]
        B, p1 = pad.b, pad.p
        zs = tuple(z.transpose(1, 0, 2)[:B] for z in zs)
        nets = (nets[0][:, :p1],) + tuple(nets[1:])
        return zs, nets
