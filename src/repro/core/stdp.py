"""Unsupervised STDP with weight-dependent stabilization (paper §II-C).

Hardware mapping (one instance of this logic per synapse in silicon):

    * ``stdp_case_gen``   — classifies the (input time x, output time z) pair
                            into capture / backoff / search / none.
    * ``stabilize_func``  — an 8-to-1 GDI mux that uses the 3-bit weight to
                            select one of 8 Bernoulli random variables (BRVs):
                            here a ``(w_max+1,)`` probability table ``F[w]``.
    * ``incdec``          — turns (case, sampled BRV) into ±1 control signals.
    * ``syn_weight_update``— the saturating 3-bit up/down counter FSM.

The four timing cases (x = input spike time, z = *post-WTA* output spike
time, T = no-spike):

    capture   x <= z, both spike     w += 1   with prob  mu_capture * F[w]
    backoff   x >  z, both spike     w -= 1   with prob  mu_backoff * F[w]
    search    x spikes, z doesn't    w += 1   with prob  mu_search
    backoff   z spikes, x doesn't    w -= 1   with prob  mu_backoff * F[w]

The stabilization table defaults to the inverted-U ``F[w] ∝ w*(w_max-w)``
(max update rate mid-range, slow at the rails) which drives weights to a
bimodal 0/w_max distribution — the "stabilized weight convergence" the
paper's ``stabilize_func`` macro exists to produce. The table is a config
field: it IS the mux contents, so any stabilization in the family is
expressible (set all-ones to disable).

Randomness: hardware BRVs come from per-synapse LFSRs; we use counter-based
threefry bits passed in explicitly, so the update is a deterministic
function of ``(weights, x, z, random_bits)`` — exactly oracle-checkable
against the Pallas kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.temporal import WaveSpec
from repro.utils import tracing


def default_stabilize_table(w_max: int) -> Tuple[float, ...]:
    """Inverted-U BRV table: F[w] ∝ w*(w_max-w), floor so rails stay live."""
    vals = []
    for w in range(w_max + 1):
        f = 4.0 * max(w * (w_max - w), 1) / (w_max * w_max)
        vals.append(min(f, 1.0))
    return tuple(vals)


@dataclasses.dataclass(frozen=True)
class STDPConfig:
    """STDP hyper-parameters (all probabilities are multiples of 1/16 in the
    hardware's 4-bit BRV generators; defaults chosen accordingly)."""

    mu_capture: float = 10.0 / 16.0
    mu_backoff: float = 6.0 / 16.0
    mu_search: float = 2.0 / 16.0
    stabilize: Tuple[float, ...] = ()
    # "sum": batched net update (one counter update per wave across the
    #        batch — the data-parallel extension, DESIGN.md §2).
    # "seq": exact silicon semantics, one image per wave via lax.scan.
    batch_reduce: str = "sum"

    def table_tuple(self, spec: WaveSpec) -> Tuple[float, ...]:
        """The BRV table as a static python tuple (the form the Pallas kernel
        takes as a compile-time constant)."""
        tab = self.stabilize or default_stabilize_table(spec.w_max)
        if len(tab) != spec.w_max + 1:
            raise ValueError(
                f"stabilize table has {len(tab)} entries, need {spec.w_max + 1}"
            )
        return tuple(float(v) for v in tab)

    def table(self, spec: WaveSpec) -> jnp.ndarray:
        return jnp.asarray(self.table_tuple(spec), dtype=jnp.float32)


def stdp_cases(x: jax.Array, z: jax.Array, T: int):
    """``stdp_case_gen``: boolean (capture, backoff, search) planes.

    x: (..., p) input spike times; z: (..., q) output spike times.
    Broadcasts to (..., p, q).
    """
    xs = x[..., :, None].astype(jnp.int32)  # (..., p, 1)
    zs = z[..., None, :].astype(jnp.int32)  # (..., 1, q)
    x_fired = xs < T
    z_fired = zs < T
    capture = x_fired & z_fired & (xs <= zs)
    backoff = (x_fired & z_fired & (xs > zs)) | (~x_fired & z_fired)
    search = x_fired & ~z_fired
    return capture, backoff, search


def stdp_update(
    weights: jax.Array,
    x: jax.Array,
    z: jax.Array,
    rng: jax.Array,
    spec: WaveSpec,
    cfg: STDPConfig,
) -> jax.Array:
    """One gamma wave of STDP. ``weights``: (p, q) int8; x: (B?, p); z: (B?, q).

    Returns updated (p, q) int8 weights, saturating at [0, w_max].
    """
    table = cfg.table(spec)
    batched = x.ndim == 2
    if not batched:
        x, z = x[None], z[None]

    if cfg.batch_reduce == "seq":

        def body(w, xz_key):
            xb, zb, key = xz_key
            return _single_wave(w, xb, zb, key, table, spec, cfg), None

        keys = jax.random.split(rng, x.shape[0])
        weights, _ = jax.lax.scan(body, weights, (x, z, keys))
        return weights

    if cfg.batch_reduce == "gauss":
        # Binomial-moment-matched batched update: instead of (2, B, p, q)
        # uniforms, count the eligible cases per synapse and sample the
        # net increment from a Gaussian with the binomial's mean/variance —
        # 2B fewer random numbers per wave, identical first two moments
        # (beyond-paper scaling mode; exact modes "sum"/"seq" retained).
        capture, backoff, search = stdp_cases(x, z, spec.T)
        f = table[weights.astype(jnp.int32)]
        n_cap = capture.astype(jnp.float32).sum(axis=0)
        n_sea = search.astype(jnp.float32).sum(axis=0)
        n_back = backoff.astype(jnp.float32).sum(axis=0)
        p_cap, p_sea, p_back = cfg.mu_capture * f, cfg.mu_search, cfg.mu_backoff * f
        mean = n_cap * p_cap + n_sea * p_sea - n_back * p_back
        var = (n_cap * p_cap * (1 - p_cap) + n_sea * p_sea * (1 - p_sea)
               + n_back * p_back * (1 - p_back))
        g = jax.random.normal(rng, mean.shape, jnp.float32)
        delta = jnp.round(mean + jnp.sqrt(var) * g).astype(jnp.int32)
        w = weights.astype(jnp.int32) + delta
        return jnp.clip(w, 0, spec.w_max).astype(jnp.int8)

    if cfg.batch_reduce != "sum":
        raise ValueError(f"unknown batch_reduce: {cfg.batch_reduce}")

    capture, backoff, search = stdp_cases(x, z, spec.T)  # (B, p, q)
    f = table[weights.astype(jnp.int32)]  # (p, q)
    p_up = capture * (cfg.mu_capture * f) + search * jnp.float32(cfg.mu_search)
    p_dn = backoff * (cfg.mu_backoff * f)
    u = jax.random.uniform(rng, (2,) + capture.shape, dtype=jnp.float32)
    inc = (u[0] < p_up).astype(jnp.int32).sum(axis=0)
    dec = (u[1] < p_dn).astype(jnp.int32).sum(axis=0)
    w = weights.astype(jnp.int32) + inc - dec
    return jnp.clip(w, 0, spec.w_max).astype(jnp.int8)


def stdp_net_from_uniforms(
    weights: jax.Array,
    x: jax.Array,
    z: jax.Array,
    u_up: jax.Array,
    u_dn: jax.Array,
    spec: WaveSpec,
    cfg: STDPConfig,
) -> jax.Array:
    """Counter form of the batched-"sum" update: net inc-dec, pre-clip.

    weights: (p, q); x: (B, p); z: (B, q); u_up/u_dn: (B, p, q) uniforms —
    the same draws the "sum" branch of :func:`stdp_update` makes internally
    (``u[0]``/``u[1]`` of a ``(2, B, p, q)`` uniform), passed in explicitly.
    Returns (p, q) i32 net counter deltas.

    This is the additive half of the update: deltas from disjoint batch
    shards sum (``psum`` over the mesh's "data" axis) before ONE saturating
    :func:`apply_net`, which makes data-parallel training produce exactly
    the full-batch result (DESIGN.md §9).
    """
    table = cfg.table(spec)
    capture, backoff, search = stdp_cases(x, z, spec.T)
    f = table[weights.astype(jnp.int32)]
    p_up = capture * (cfg.mu_capture * f) + search * jnp.float32(cfg.mu_search)
    p_dn = backoff * (cfg.mu_backoff * f)
    inc = (u_up < p_up).astype(jnp.int32).sum(axis=0)
    dec = (u_dn < p_dn).astype(jnp.int32).sum(axis=0)
    return inc - dec


def apply_net(weights: jax.Array, net: jax.Array, spec: WaveSpec) -> jax.Array:
    """Saturating counter apply: clip(w + net, 0, w_max) as int8 — the
    ``syn_weight_update`` FSM once per wave, after counter aggregation."""
    with jax.named_scope(tracing.APPLY):
        return jnp.clip(weights.astype(jnp.int32) + net, 0,
                        spec.w_max).astype(jnp.int8)


def _single_wave(w, x, z, key, table, spec: WaveSpec, cfg: STDPConfig):
    capture, backoff, search = stdp_cases(x, z, spec.T)
    f = table[w.astype(jnp.int32)]
    p_up = capture * (cfg.mu_capture * f) + search * jnp.float32(cfg.mu_search)
    p_dn = backoff * (cfg.mu_backoff * f)
    u = jax.random.uniform(key, (2,) + capture.shape, dtype=jnp.float32)
    delta = (u[0] < p_up).astype(jnp.int32) - (u[1] < p_dn).astype(jnp.int32)
    return jnp.clip(w.astype(jnp.int32) + delta, 0, spec.w_max).astype(jnp.int8)
