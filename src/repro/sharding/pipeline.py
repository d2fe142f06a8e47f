"""Pipeline parallelism over the "pod" axis (GPipe-style microbatching).

The multi-pod mesh's cross-pod links (DCI) are much slower than ICI, so the
pod axis should carry either pure gradient reduction (the default DP/FSDP
mapping) or *pipeline* traffic — one boundary activation per microbatch —
which is what this module provides.

Mechanics (classic GPipe on an SPMD mesh):
  * the stacked per-layer params (R, ...) are sharded on the layer axis
    over "pod": stage s physically holds layers [s·R/P, (s+1)·R/P);
  * inside ``shard_map`` every pod runs the same program over
    ``n_micro + P - 1`` ticks; at each tick a pod applies its local layers
    to its current activation and passes the result to the next pod with
    ``lax.ppermute`` (the bubble is masked compute);
  * microbatch m enters stage 0 at tick m and exits stage P-1 at tick
    m + P - 1; outputs are collected where valid. Gradients flow through
    the transposed ppermute automatically, so ``jax.grad`` of a pipelined
    forward is the pipelined backward.

This composes with the in-stage sharding: "data"/"model" axes stay GSPMD-
managed (shard_map ``auto``). Equivalence to sequential execution is
asserted in tests/test_pipeline.py.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding import shard_map


def pipeline_apply(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    n_micro: int,
    axis: str = "pod",
):
    """Run ``layer_fn(params_r, x)`` for r = 0..R-1 as a P-stage pipeline.

    stacked_params: pytree with leading layer axis R (R % P == 0), sharded
        over ``axis`` on that leading dimension.
    x: (B, ...) global batch; B % n_micro == 0. Returns f(x) identical to
        the sequential composition of all R layers.
    """
    P_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro

    other_axes = frozenset(a for a in mesh.axis_names if a != axis)

    def staged(local_params, xm):
        # local_params: (R/P, ...) this stage's layers; xm: (n_micro, mb, ...)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + P_stages - 1

        def apply_local(h):
            def body(carry, pr):
                return layer_fn(pr, carry), None
            out, _ = jax.lax.scan(body, h, local_params)
            return out

        def tick(carry, t):
            buf, outs = carry  # buf: (mb, ...) activation entering this stage
            # stage 0 ingests microbatch t (masked when t >= n_micro)
            feed = xm[jnp.minimum(t, n_micro - 1)]
            h = jnp.where(stage == 0, feed, buf)
            h = apply_local(h)
            # pass to next stage; last stage's output wraps to stage 0 (ignored)
            perm = [(i, (i + 1) % P_stages) for i in range(P_stages)]
            nxt = jax.lax.ppermute(h, axis, perm)
            # microbatch m exits the last stage at tick m + P - 1
            m = t - (P_stages - 1)
            valid = (stage == P_stages - 1) & (m >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, h[None], jnp.maximum(m, 0), axis=0),
                lambda o: o,
                outs)
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xm[0])
        outs0 = jnp.zeros_like(xm)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
        # only the last stage wrote non-zeros: psum replicates its outputs
        # to every pod (downstream consumers are unsharded on "pod")
        return jax.lax.psum(outs, axis)

    xm = x.reshape((n_micro, mb) + x.shape[1:])
    # commit the operands to the mesh first: under a mesh with Explicit
    # axes (``jax.make_mesh``'s default) a shard_map over arrays that sit
    # on one device has no device assignment for the whole mesh
    stacked_params = jax.device_put(stacked_params,
                                    NamedSharding(mesh, P(axis)))
    xm = jax.device_put(xm, NamedSharding(mesh, P()))
    fn = shard_map(
        staged, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_replication=False,
    )
    out = fn(stacked_params, xm)
    return out.reshape((B,) + out.shape[2:])
