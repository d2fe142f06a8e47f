"""Public jit'd wrappers around the Pallas kernels — the production TNN path.

The raw kernels (:mod:`repro.kernels.tnn_column`, :mod:`repro.kernels.wta`,
:mod:`repro.kernels.stdp_update`) require tile-aligned shapes; the wrappers
here make them safe for arbitrary shapes and both execution targets:

* **Padding semantics** (DESIGN.md §6). Batch rows and synapse rows are
  padded up to block multiples before the kernel launch and sliced away
  after. The geometry AND the no-op pad encodings (spikes=T, weight rows=0,
  uniforms=1.0) live in one place — :class:`repro.kernels.padding.PadPlan` —
  instead of being recomputed ad hoc in every wrapper.

* **``interpret`` by backend** (DESIGN.md §8). Every wrapper takes
  ``interpret: bool | None``. ``None`` (the default) resolves from
  ``jax.default_backend()``: Mosaic on ``tpu``, the (slow but bit-exact)
  Pallas interpreter on ``cpu`` — the test backend — and an error on any
  other backend, so no run silently falls back to the interpreter.

Layer-level entry points (:func:`layer_forward_fused`,
:func:`layer_stdp_fused`) pad ONCE for the whole ``(B, n_cols, p)`` layer,
go column-major, and launch ONE kernel whose leading grid dimension is the
column axis — the same layout the fused wave uses. The single-column
wrappers are the ``C = 1`` case of the same kernels.

Usage — fused forward + learning for one layer (CPU or TPU)::

    import jax, jax.numpy as jnp
    from repro.core.stdp import default_stabilize_table
    from repro.kernels import ops

    B, C, p, q, T, theta = 32, 625, 32, 12, 8, 24
    x = jax.random.randint(jax.random.PRNGKey(0), (B, C, p), 0, T + 1, jnp.int8)
    w = jax.random.randint(jax.random.PRNGKey(1), (C, p, q), 0, 8, jnp.int8)

    z = ops.layer_forward_fused(x, w, theta=theta, T=T)        # (B, C, q) i32
    u = jax.random.uniform(jax.random.PRNGKey(2), (C, 2, B, p, q))
    w2 = ops.layer_stdp_fused(w, x, z, u[:, 0], u[:, 1], T=T, w_max=7,
                              table=default_stabilize_table(7))

In the core model the same path is selected declaratively with
``ColumnConfig(impl="pallas")`` — see :mod:`repro.core.layer`. The
whole-network single-launch wave executor (``impl="fused"``) lives in
:mod:`repro.kernels.tnn_wave` (DESIGN.md §10).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.padding import PadPlan, pad_to
from repro.kernels.stdp_update import stdp_update_pallas
from repro.kernels.tnn_column import column_forward_pallas
from repro.kernels.wta import wta_pallas
from repro.utils import tracing


# VMEM for one STDP uniforms block, (1, block_b, block_p, q) f32 with q in
# 128-lane tiles. The kernel double-buffers the up and down blocks, so four
# of them lie in VMEM at once: 8 MiB of v5e's 16 MiB scoped default.
STDP_U_BLOCK_BYTES = 2 << 20


def stdp_block_b(p: int, q: int, *, block_b: int, block_p: int) -> int:
    """The STDP launch's batch block: ``block_b``, or the most rows, a
    multiple of 8, with which one uniforms block fits
    :data:`STDP_U_BLOCK_BYTES`. Narrow fan-ins keep ``block_b`` (every
    prototype layer at any batch up to 128); a wide one (1,882 synapses a
    column) takes fewer rows a tile."""
    rows = min(block_p, pad_to(p, 8)) * pad_to(q, 128) * 4
    fit = max(STDP_U_BLOCK_BYTES // rows // 8 * 8, 8)
    return min(block_b, fit)


def column_forward(
    x: jax.Array,
    w: jax.Array,
    *,
    theta: int,
    T: int = 8,
    wta: bool = False,
    block_b: int = 64,
    block_p: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused column forward (+ optional WTA). x: (B, p), w: (p, q) -> (B, q) i32."""
    B, p = x.shape
    q = w.shape[1]
    plan = PadPlan.make(B, p, block_b=block_b, block_p=block_p,
                        interpret=interpret)
    x = plan.pad_spikes(x, T, p_axis=1)
    w = plan.pad_weights(w)
    z = column_forward_pallas(
        x[None], w[None], theta=theta, T=T, wta=wta,
        block_b=plan.block_b, block_p=plan.block_p, interpret=plan.interpret,
    )
    return z[0, :B, :q]


def wta(z: jax.Array, *, T: int = 8, block_b: int = 128, interpret: bool | None = None) -> jax.Array:
    """Post-forward WTA inhibition. z: (B, q) -> (B, q) i32."""
    B = z.shape[0]
    plan = PadPlan.make(B, block_b=block_b, interpret=interpret)
    z = plan.pad_spikes(z, T)
    return wta_pallas(z, T=T, block_b=plan.block_b,
                      interpret=plan.interpret)[:B]


def stdp_update(
    w: jax.Array,
    x: jax.Array,
    z: jax.Array,
    u_up: jax.Array,
    u_dn: jax.Array,
    *,
    T: int = 8,
    w_max: int = 7,
    table: tuple,
    mu_capture: float = 10 / 16,
    mu_backoff: float = 6 / 16,
    mu_search: float = 2 / 16,
    block_p: int = 128,
    block_b: int = 128,
    interpret: bool | None = None,
    out: str = "weights",
) -> jax.Array:
    """Fused STDP wave update. Returns new (p, q) i32 weights, or the raw
    pre-clip (p, q) i32 net counters when ``out="net"`` (DESIGN.md §9)."""
    B, p = x.shape
    block_b = stdp_block_b(p, w.shape[1], block_b=block_b, block_p=block_p)
    plan = PadPlan.make(B, p, block_b=block_b, block_p=block_p,
                        interpret=interpret)
    # padded batch rows: x=T & z=T -> 'none' case -> no update; padded
    # synapse rows carry u=1.0 and are sliced away.
    x = plan.pad_spikes(x, T, p_axis=1)
    z = plan.pad_spikes(z, T)
    w = plan.pad_weights(w)
    u_up = plan.pad_uniforms(u_up, p_axis=1)
    u_dn = plan.pad_uniforms(u_dn, p_axis=1)
    res = stdp_update_pallas(
        w[None], x[None], z[None], u_up[None], u_dn[None],
        T=T, w_max=w_max, table=tuple(table),
        mu_capture=mu_capture, mu_backoff=mu_backoff, mu_search=mu_search,
        block_p=plan.block_p, block_b=plan.block_b, interpret=plan.interpret,
        out=out,
    )
    return res[0, :p]


def layer_forward_fused(
    x: jax.Array,
    w: jax.Array,
    *,
    theta: int,
    T: int = 8,
    wta: bool = True,
    block_b: int = 64,
    block_p: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Whole-layer fused forward+WTA: x (B, C, p), w (C, p, q) -> (B, C, q) i32.

    Pads the batch/synapse axes once for the whole layer (see the module
    docstring for the no-op encodings) and goes column-major — the layer's
    spatial replication (Fig. 1) becomes the leading grid dimension of one
    kernel launch.
    """
    B, _, p = x.shape
    plan = PadPlan.make(B, p, block_b=block_b, block_p=block_p,
                        interpret=interpret)
    with jax.named_scope(tracing.WAVE):
        x = plan.pad_spikes(x, T, p_axis=2).transpose(1, 0, 2)
        w = plan.pad_weights(w, p_axis=1)
        z = column_forward_pallas(
            x, w, theta=theta, T=T, wta=wta, block_b=plan.block_b,
            block_p=plan.block_p, interpret=plan.interpret,
        )
        return z.transpose(1, 0, 2)[:B]


def layer_stdp_fused(
    w: jax.Array,
    x: jax.Array,
    z: jax.Array,
    u_up: jax.Array,
    u_dn: jax.Array,
    *,
    T: int = 8,
    w_max: int = 7,
    table: tuple,
    mu_capture: float = 10 / 16,
    mu_backoff: float = 6 / 16,
    mu_search: float = 2 / 16,
    block_p: int = 128,
    block_b: int = 128,
    interpret: bool | None = None,
    out: str = "weights",
) -> jax.Array:
    """Whole-layer fused STDP: one wave of learning for every column at once.

    w: (C, p, q) weights; x: (B, C, p) inputs; z: (B, C, q) post-WTA outputs;
    u_up/u_dn: (C, B, p, q) per-column uniforms (column-major so each column's
    draws match the reference path's per-column rng split). Returns (C, p, q)
    i32 weights. Padding happens once at the layer level — padded batch rows
    carry u=1.0 so they can never win a Bernoulli compare.

    ``out="net"`` returns the pre-clip (C, p, q) i32 batch-summed counter
    deltas instead of applied weights — the additive form the sharded train
    step psums over the mesh's "data" axis (DESIGN.md §9).
    """
    B, _, p = x.shape
    block_b = stdp_block_b(p, w.shape[2], block_b=block_b, block_p=block_p)
    plan = PadPlan.make(B, p, block_b=block_b, block_p=block_p,
                        interpret=interpret)
    with jax.named_scope(tracing.WAVE):
        x = plan.pad_spikes(x, T, p_axis=2).transpose(1, 0, 2)
        z = plan.pad_spikes(z, T).transpose(1, 0, 2)
        w = plan.pad_weights(w, p_axis=1)
        u_up = plan.pad_uniforms(u_up, b_axis=1, p_axis=2)
        u_dn = plan.pad_uniforms(u_dn, b_axis=1, p_axis=2)
        res = stdp_update_pallas(
            w, x, z, u_up, u_dn,
            T=T, w_max=w_max, table=tuple(table),
            mu_capture=mu_capture, mu_backoff=mu_backoff,
            mu_search=mu_search, block_p=plan.block_p, block_b=plan.block_b,
            interpret=plan.interpret, out=out,
        )
        return res[:, :p]
