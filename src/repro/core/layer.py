"""Multi-column TNN layers (Fig. 1: a layer is a grid of identical columns).

A layer holds ``n_cols`` columns of identical (p, q) shape; weights are a
single ``(n_cols, p, q)`` int8 array and every column runs the same pure
``column_step`` — the silicon's spatial replication becomes ``vmap``.

Execution backend is selected by ``ColumnConfig.impl``: the two reference
formulations ("direct"/"matmul") vmap per-column jnp code, while "pallas"
routes the whole layer through the fused kernels in :mod:`repro.kernels`
(one padded launch per layer, bit-exact with the reference — DESIGN.md §2).
"fused" selects the whole-network single-launch wave executor, which is a
NETWORK-level fusion (:mod:`repro.core.network` dispatches it); at layer
granularity it is identical to "pallas" — that is also the fallback for
networks outside the fused executor's same-site N-layer chain topology
(DESIGN.md §10, §11).

Also provides the receptive-field plumbing for the MNIST prototype: 4x4
pixel patches x {on, off} polarity = 32 synapses per column, 25x25 = 625
sites over a 28x28 field (Fig. 19).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.column import (
    ColumnConfig, column_forward, column_forward_matmul, init_weights, wta_inhibit,
)
from repro.core.stdp import stdp_net_from_uniforms, stdp_update
from repro.core.temporal import SPIKE_DTYPE, WaveSpec
from repro.kernels import ops as _kops
from repro.utils import tracing


@dataclasses.dataclass(frozen=True)
class LayerConfig:
    n_cols: int
    column: ColumnConfig

    def validate(self) -> None:
        if self.n_cols < 1:
            raise ValueError(f"n_cols={self.n_cols}")
        self.column.validate()

    @property
    def n_neurons(self) -> int:
        return self.n_cols * self.column.q

    @property
    def n_synapses(self) -> int:
        return self.n_cols * self.column.p * self.column.q


def init_layer(rng: jax.Array, cfg: LayerConfig) -> jax.Array:
    keys = jax.random.split(rng, cfg.n_cols)
    return jax.vmap(lambda k: init_weights(k, cfg.column.p, cfg.column.q, cfg.column.wave))(keys)


def layer_forward(x: jax.Array, w: jax.Array, cfg: LayerConfig) -> jax.Array:
    """x: (B, n_cols, p) -> post-WTA spike times (B, n_cols, q)."""
    spec = cfg.column.wave
    if cfg.column.impl in ("pallas", "fused"):
        with jax.named_scope(tracing.WAVE):
            z = _kops.layer_forward_fused(x, w, theta=cfg.column.theta,
                                          T=spec.T)
            return z.astype(SPIKE_DTYPE)
    fwd = column_forward_matmul if cfg.column.impl == "matmul" else column_forward

    def one_col(xc, wc):
        return wta_inhibit(fwd(xc, wc, cfg.column.theta, spec), spec)

    # vmap over columns (axis 1 of x, axis 0 of w)
    return jax.vmap(one_col, in_axes=(1, 0), out_axes=1)(x, w)


def layer_uniforms(key: jax.Array, cfg: LayerConfig, B: int) -> jax.Array:
    """One wave's STDP uniforms for a whole layer: (n_cols, 2, B, p, q),
    drawn from the per-column key split EVERY backend uses — the per-layer
    vmap path, the layer-level pallas kernels and the whole-network fused
    wave executor all consume these exact draws (u[:, 0] = up, u[:, 1] =
    down), which is what makes their updates bit-identical.

    Each column draws (2, B, p*q) and only then reshapes to (2, B, p, q):
    threefry's counter is the row-major flat index, so the bits are those
    of drawing (2, B, p, q) directly. The barrier keeps the compiler from
    folding the reshape back into the draw, whose (p, q)-minor layout
    would fill 12 or 10 of a TPU tile's 128 lanes."""
    p, q = cfg.column.p, cfg.column.q
    with jax.named_scope(tracing.UNIFORMS):
        col_keys = jax.random.split(key, cfg.n_cols)
        u = jax.vmap(
            lambda kk: jax.random.uniform(kk, (2, B, p * q), dtype=jnp.float32)
        )(col_keys)
        u = jax.lax.optimization_barrier(u)
        return u.reshape(cfg.n_cols, 2, B, p, q)


def layer_step(
    x: jax.Array,
    w: jax.Array,
    cfg: LayerConfig,
    rng: Optional[jax.Array] = None,
    learn: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """One gamma wave for the whole layer. x: (B, n_cols, p)."""
    z = layer_forward(x, w, cfg)
    if learn:
        if rng is None:
            raise ValueError("learning requires rng")
        keys = jax.random.split(rng, cfg.n_cols)
        spec, stdp = cfg.column.wave, cfg.column.stdp
        if cfg.column.impl in ("pallas", "fused") and stdp.batch_reduce == "sum":
            # Fused layer-level STDP. The uniforms come from layer_uniforms
            # — the SAME per-column key split and (2, B, p, q) shape as the
            # reference stdp_update, so the Bernoulli compares see identical
            # bits -> the update is bit-exact with the vmap path
            # ("seq"/"gauss" reduce modes keep the reference path; the
            # fused kernel implements the batched-sum counters).
            u = layer_uniforms(rng, cfg, x.shape[0])  # (n_cols, 2, B, p, q)
            w = _kops.layer_stdp_fused(
                w, x, z, u[:, 0], u[:, 1],
                T=spec.T, w_max=spec.w_max, table=stdp.table_tuple(spec),
                mu_capture=stdp.mu_capture, mu_backoff=stdp.mu_backoff,
                mu_search=stdp.mu_search,
            ).astype(jnp.int8)
            return z, w
        w = jax.vmap(
            lambda wc, xc, zc, k: stdp_update(wc, xc, zc, k, spec, stdp),
            in_axes=(0, 1, 1, 0),
        )(w, x, z, keys)
    return z, w


def layer_stdp_net(
    x: jax.Array,
    z: jax.Array,
    w: jax.Array,
    cfg: LayerConfig,
    u_up: jax.Array,
    u_dn: jax.Array,
) -> jax.Array:
    """Net STDP counter deltas for a whole layer, pre-clip (DESIGN.md §9).

    x: (B, C, p) inputs; z: (B, C, q) post-WTA outputs; w: (C, p, q) int8;
    u_up/u_dn: (C, B, p, q) per-column uniforms (the explicit-uniform form of
    the "sum" batch reduce). Returns (C, p, q) i32 deltas that sum across
    disjoint batch shards; apply once with :func:`repro.core.stdp.apply_net`.

    Backend follows ``cfg.column.impl``: "pallas" runs the fused kernel in
    net mode (one padded launch for the layer), the references vmap the pure
    counter form per column — bit-exact with each other and with the applied
    update of :func:`layer_step`.
    """
    spec, stdp = cfg.column.wave, cfg.column.stdp
    if stdp.batch_reduce != "sum":
        raise ValueError(
            f"counter-form STDP requires batch_reduce='sum', got "
            f"{stdp.batch_reduce!r} ('seq'/'gauss' do not decompose into "
            f"shard-additive counters)")
    if cfg.column.impl in ("pallas", "fused"):
        return _kops.layer_stdp_fused(
            w, x, z, u_up, u_dn,
            T=spec.T, w_max=spec.w_max, table=stdp.table_tuple(spec),
            mu_capture=stdp.mu_capture, mu_backoff=stdp.mu_backoff,
            mu_search=stdp.mu_search, out="net",
        )
    return jax.vmap(
        lambda wc, xc, zc, uu, ud: stdp_net_from_uniforms(
            wc, xc, zc, uu, ud, spec, stdp),
        in_axes=(0, 1, 1, 0, 0),
    )(w, x, z, u_up, u_dn)


# ---------------------------------------------------------------------------
# Receptive-field extraction (the prototype's patch front end)
# ---------------------------------------------------------------------------


def extract_patches(images: jax.Array, k: int, stride: int = 1) -> jax.Array:
    """(B, H, W) -> (B, sites, k*k) sliding patches (valid padding).

    28x28 with k=4, stride=1 -> 625 sites of 16 pixels, matching Fig. 19's
    625 columns x (16 px x 2 polarities = 32 synapses).
    """
    B, H, W = images.shape
    oh, ow = (H - k) // stride + 1, (W - k) // stride + 1
    patches = jax.lax.conv_general_dilated_patches(
        images[:, None, :, :].astype(jnp.float32),
        filter_shape=(k, k),
        window_strides=(stride, stride),
        padding="VALID",
    )  # (B, k*k, oh, ow)
    return patches.reshape(B, k * k, oh * ow).transpose(0, 2, 1)


def encode_patches_onoff(patches01: jax.Array, spec: WaveSpec) -> jax.Array:
    """Pixel intensities in [0,1] -> interleaved on/off spike times.

    (B, sites, px) -> (B, sites, 2*px) uint8; this is the DoG-style
    two-polarity front end feeding layer 1 (DESIGN.md §1).
    """
    on = jnp.round((1.0 - jnp.clip(patches01, 0, 1)) * spec.T)
    off = jnp.round(jnp.clip(patches01, 0, 1) * spec.T)
    out = jnp.stack([on, off], axis=-1).reshape(*patches01.shape[:-1], patches01.shape[-1] * 2)
    return out.astype(SPIKE_DTYPE)
