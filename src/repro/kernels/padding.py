"""Launch geometry and padding plans for the Pallas TNN kernels.

Every kernel wrapper in this package shares the same launch prologue: clamp
the block sizes to the 8-aligned problem extents, pad the batch / synapse
axes up to block multiples, launch, slice the padding away. Before this
module the pad/slice boilerplate was copied (with per-layout axis tweaks)
across ``column_forward`` / ``wta`` / ``stdp_update`` /
``layer_forward_fused`` / ``layer_stdp_fused``; a :class:`PadPlan` computes
the geometry ONCE and owns the no-op pad encodings (DESIGN.md §6):

  - padded *spike times* are ``T`` ("no spike"): an RNL ramp that never
    starts contributes 0 to every body potential, and the STDP case
    generator classifies an (x=T, z=T) pair as "none" (no update);
  - padded *weight rows* are 0: a zero-weight synapse saturates its ramp
    at 0, and padded output rows are sliced off before anything reads them;
  - padded *STDP uniforms* are 1.0: a Bernoulli compare ``u < p`` with
    ``u = 1.0`` never fires, so padded batch rows cannot perturb counters.

:func:`network_plan` lifts the same idea to the whole network for the fused
wave executor (:mod:`repro.kernels.tnn_wave`, DESIGN.md §10): one
:class:`NetworkPlan` per ``(NetworkConfig, batch)`` — computed once,
lru-cached on the frozen config — carries the padded extents, block sizes
and the static per-layer STDP constants the megakernel compiles against.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The Pallas ``interpret`` flag for this process: an explicit value
    wins; ``None`` picks Mosaic on ``tpu`` and the bit-exact interpreter on
    ``cpu`` (the test backend). Any other backend raises — a kernel never
    falls back to the interpreter where a device was expected."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas lowering for backend {backend!r}: the TNN kernels run "
        f"via Mosaic on 'tpu' or in the interpreter on 'cpu'")


def pad_to(n: int, m: int) -> int:
    """Round ``n`` up to a multiple of ``m``."""
    return (n + m - 1) // m * m


def _pad_axis(arr: jax.Array, axis: int, amount: int, value) -> jax.Array:
    if amount == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, amount)
    return jnp.pad(arr, widths, constant_values=value)


@dataclasses.dataclass(frozen=True)
class PadPlan:
    """One launch's geometry: logical extents, clamped blocks, padded
    extents, resolved ``interpret`` flag. Frozen + hashable, so it can ride
    through ``jax.jit`` as a static argument."""

    b: int                 # logical batch rows
    p: int                 # logical synapse rows (0 when the launch has none)
    block_b: int
    block_p: int
    bp: int                # padded batch extent (multiple of block_b)
    pp: int                # padded synapse extent (multiple of block_p)
    interpret: bool

    @classmethod
    def make(
        cls,
        b: int,
        p: Optional[int] = None,
        *,
        block_b: int = 64,
        block_p: int = 256,
        p_align: int = 8,
        interpret: Optional[bool] = None,
    ) -> "PadPlan":
        """Clamp block sizes to the aligned problem extents, compute the
        padded extents, and resolve ``interpret`` by backend
        (:func:`resolve_interpret`: Mosaic on ``tpu``, the bit-exact
        interpreter on ``cpu``, an error elsewhere — DESIGN.md §6, §8). ``p_align`` widens the synapse-axis alignment
        above the tiling-minimum 8 — the autotuner's p1-pad knob
        (DESIGN.md §14): a larger alignment trades pad rows (all no-op
        encoded) for rounder VMEM tiles."""
        interpret = resolve_interpret(interpret)
        block_b = min(block_b, pad_to(b, 8))
        if p is None:
            p = block_p = pp = 0
        else:
            block_p = min(block_p, pad_to(p, max(p_align, 8)))
            pp = pad_to(p, block_p)
        return cls(b=b, p=p, block_b=block_b, block_p=block_p,
                   bp=pad_to(b, block_b), pp=pp, interpret=interpret)

    @property
    def n_b(self) -> int:
        """Batch-tile count of the launch grid."""
        return self.bp // self.block_b

    # -- the three no-op pad encodings -------------------------------------

    def pad_spikes(self, x: jax.Array, T: int, *, b_axis: Optional[int] = 0,
                   p_axis: Optional[int] = None) -> jax.Array:
        """Pad spike-time rows with ``T`` (= "no spike") on the batch and/or
        synapse axes."""
        if b_axis is not None:
            x = _pad_axis(x, b_axis, self.bp - self.b, T)
        if p_axis is not None:
            x = _pad_axis(x, p_axis, self.pp - self.p, T)
        return x

    def pad_weights(self, w: jax.Array, *, p_axis: int = 0) -> jax.Array:
        """Pad weight rows with 0 (a zero-weight synapse is a no-op)."""
        return _pad_axis(w, p_axis, self.pp - self.p, 0)

    def pad_uniforms(self, u: jax.Array, *, b_axis: int = 0,
                     p_axis: Optional[int] = None) -> jax.Array:
        """Pad STDP uniforms with 1.0 (``u < p`` can never fire)."""
        u = _pad_axis(u, b_axis, self.bp - self.b, 1.0)
        if p_axis is not None:
            u = _pad_axis(u, p_axis, self.pp - self.p, 1.0)
        return u


def pad_batch_rows(x: jax.Array, rows: int, T: int) -> jax.Array:
    """Pad the leading (batch) axis of encoded spike times up to ``rows``
    with the no-op encoding ``T`` ("never spikes").

    The shared ragged-tail helper for every fixed-shape wave batch outside
    the kernels themselves: serving (``TNNEngine`` staging partial waves and
    ``fit`` chunks, DESIGN.md §12) and evaluation
    (``TNNTrainer._forward_all``) pad through this ONE function, so a
    padded row is bit-inert on every backend — an all-``T`` volley starts
    no ramps, crosses no threshold, and exits the cascade still all ``T``.
    """
    k = x.shape[0]
    if k > rows:
        raise ValueError(f"batch of {k} rows exceeds padded extent {rows}")
    return _pad_axis(x, 0, rows - k, T)


# ---------------------------------------------------------------------------
# 2-D ("data" x "model") mesh spec: per-shard site geometry (DESIGN.md §16)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """The sharding contract every step factory consumes (DESIGN.md §16).

    One frozen record replaces the copy-pasted ``P(), P("data")`` blocks:
    how many shards each mesh axis holds, which axis names exist on the
    mesh (a 1-D ``("data",)`` host mesh simply has no model axis), and the
    per-shard SITE geometry — the logical column count padded up to a
    model-axis multiple with the SAME no-op encodings :class:`PadPlan`
    owns (pad spikes = ``T``, pad weights = 0, pad uniforms = 1.0), so a
    pad site starts no ramps, wins no WTA, and fires no STDP case: its
    weights stay 0 through any number of waves and slicing it off is
    lossless. Batch rows shard over "data", sites over "model"; the
    cascade is same-site, so the model axis needs NO inter-layer
    collective — only the data-axis counter psum crosses the wire.
    """

    n_data: int = 1
    n_model: int = 1
    n_cols: int = 0                       # logical (global) site count
    data_axis: Optional[str] = None       # None <=> axis absent from mesh
    model_axis: Optional[str] = None

    @classmethod
    def from_mesh(cls, mesh, n_cols: int) -> "MeshSpec":
        """Read the (data, model) factorization off a ``Mesh`` (either
        axis may be absent — a legacy 1-D data mesh yields n_model=1);
        ``mesh=None`` is the unsharded spec."""
        if mesh is None:
            return cls(n_cols=n_cols)
        shape = dict(mesh.shape)
        return cls(
            n_data=int(shape.get("data", 1)),
            n_model=int(shape.get("model", 1)),
            n_cols=n_cols,
            data_axis="data" if "data" in shape else None,
            model_axis="model" if "model" in shape else None,
        )

    # -- per-shard site geometry ------------------------------------------

    @property
    def padded_cols(self) -> int:
        """Site extent padded up to a model-axis multiple."""
        return pad_to(self.n_cols, self.n_model)

    @property
    def local_cols(self) -> int:
        """Sites per model shard."""
        return self.padded_cols // self.n_model

    @property
    def site_pad(self) -> int:
        """No-op pad sites appended so the model axis divides evenly."""
        return self.padded_cols - self.n_cols

    # -- PartitionSpecs ----------------------------------------------------

    def x_spec(self, leading: int = 0):
        """Spec for a spike/volley array shaped (``leading`` wave axes,
        batch, sites, ...): batch over "data", sites over "model"."""
        from jax.sharding import PartitionSpec as P

        return P(*(None,) * leading, self.data_axis, self.model_axis)

    def params_spec(self):
        """Prefix spec for a per-layer weight pytree ((sites, p, q) leaves):
        the leading site axis shards over "model", the rest replicate."""
        from jax.sharding import PartitionSpec as P

        return P(self.model_axis) if self.model_axis else P()

    def state_spec(self):
        """Prefix spec for the training-state pytree: params site-sharded
        over "model", the rng key and wave counter replicated."""
        from jax.sharding import PartitionSpec as P

        return {"params": self.params_spec(), "rng": P(), "wave": P()}

    def replicated(self):
        from jax.sharding import PartitionSpec as P

        return P()

    # -- no-op site padding / slicing (outside shard_map, inside jit) ------

    def pad_spike_sites(self, x: jax.Array, T: int, *, axis: int) -> jax.Array:
        """Pad the site axis of encoded spikes with ``T`` ("no spike")."""
        return _pad_axis(x, axis, self.site_pad, T)

    def slice_sites(self, arr: jax.Array, *, axis: int) -> jax.Array:
        """Drop the pad sites again (inverse of the pad_* helpers)."""
        if not self.site_pad:
            return arr
        return jax.lax.slice_in_dim(arr, 0, self.n_cols, axis=axis)

    def pad_weights(self, params) -> list:
        """Pad every layer's site axis (axis 0) with 0-weight no-op sites."""
        return [_pad_axis(w, 0, self.site_pad, 0) for w in params]

    def pad_params_tree(self, tree: dict) -> dict:
        return {k: _pad_axis(w, 0, self.site_pad, 0) for k, w in tree.items()}

    def slice_params_tree(self, tree: dict) -> dict:
        return {k: self.slice_sites(w, axis=0) for k, w in tree.items()}


def pad_uniform_sites(u: jax.Array, padded_cols: int) -> jax.Array:
    """Pad the leading site axis of per-layer STDP uniforms up to
    ``padded_cols`` with the no-op 1.0 (``u < p`` never fires), so pad
    sites draw no stochastic update and every real site keeps the exact
    global-draw value regardless of the model factorization."""
    return _pad_axis(u, 0, padded_cols - u.shape[0], 1.0)


# ---------------------------------------------------------------------------
# Network-level plan for the fused wave executor (DESIGN.md §10, §11)
# ---------------------------------------------------------------------------

# The megakernel keeps each column's layer-1 synapse axis in ONE tile (the
# whole wave runs without an inter-tile reduction), so padded p1 is capped.
# Deeper layers' fan-ins are previous layers' neuron counts (<= 128 lanes),
# so only the input-facing synapse axis ever needs this cap.
MAX_FUSED_P1 = 512


@dataclasses.dataclass(frozen=True)
class NetworkPlan:
    """Static compile plan for one fused gamma wave over an N-layer
    same-site cascade: padded extents + every per-layer constant the
    megakernel needs as a compile-time value, in layer order. Hashable —
    passed to ``jax.jit`` as static, so the per-layer geometry is unrolled
    from the plan at trace time (DESIGN.md §11)."""

    n_cols: int
    ps: Tuple[int, ...]          # logical fan-in per layer (ps[i] = qs[i-1])
    qs: Tuple[int, ...]          # neurons per layer
    thetas: Tuple[int, ...]      # firing threshold per layer
    T: int
    w_max: int
    pad: PadPlan                 # batch axis + layer-1 synapse axis
    # static STDP constants per layer: stabilize table + (capture, backoff,
    # search) rates — the Bernoulli side of the counter epilogue.
    tables: Tuple[Tuple[float, ...], ...]
    mus: Tuple[Tuple[float, float, float], ...]
    # Bit-packed kernel IO (DESIGN.md §14): spike volleys cross the launch
    # boundary as uint8 and weights as int8, widening to i32 only inside
    # the kernel; False keeps the legacy widen-before-launch i32 layout.
    packed: bool = False

    @property
    def n_layers(self) -> int:
        return len(self.qs)

    @property
    def pps(self) -> Tuple[int, ...]:
        """Padded fan-in extent per layer: the input-facing synapse axis is
        padded to the plan's single tile; deeper fan-ins are inter-layer
        volleys that never leave VMEM, so they stay at logical extent."""
        return (self.pad.pp,) + self.ps[1:]


def fused_wave_capable(cfg) -> bool:
    """Whether ``cfg`` (a ``core.network.NetworkConfig``) matches the fused
    wave executor's topology: an N-layer (N >= 1) cascade of same-site
    layers chained so each layer's fan-in is the previous layer's neuron
    count, one shared wave spec, and extents the single-tile megakernel can
    hold (every q <= 128 lanes, padded p1 <= ``MAX_FUSED_P1``). Networks
    outside this shape run ``impl="fused"`` as per-layer pallas launches
    instead (DESIGN.md §10, §11)."""
    layers = cfg.layers
    if not layers:
        return False
    first = layers[0]
    if pad_to(first.column.p, 8) > MAX_FUSED_P1:
        return False
    prev_q = None
    for l in layers:
        if (l.n_cols != first.n_cols
                or l.column.wave != first.column.wave
                or l.column.q > 128):
            return False
        if prev_q is not None and l.column.p != prev_q:
            return False
        prev_q = l.column.q
    return True


def plan_geometry_key(cfg, batch: int, n_cols: Optional[int] = None) -> str:
    """Stable string naming a fused-wave launch geometry — the lookup key
    of the autotuner's block cache (``benchmarks/tuned_blocks.json``,
    DESIGN.md §14). Deliberately covers ONLY what changes the launch shape
    (sites, per-layer extents, T, batch, packed IO), not thetas/STDP rates:
    the same silicon geometry at different hyperparameters reuses one tuned
    entry. ``n_cols`` overrides the config's site count — the model-sharded
    step launches over its LOCAL site slice (DESIGN.md §16), which is a
    different grid and therefore a different tuning key."""
    first = cfg.layers[0]
    C = first.n_cols if n_cols is None else n_cols
    ps = "x".join(str(l.column.p) for l in cfg.layers)
    qs = "x".join(str(l.column.q) for l in cfg.layers)
    packed = int(bool(getattr(cfg, "packed", False)))
    return (f"C{C}_p{ps}_q{qs}_T{first.column.wave.T}"
            f"_B{batch}_packed{packed}")


@functools.lru_cache(maxsize=64)
def network_plan(cfg, batch: int, block_b: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 n_cols: Optional[int] = None) -> NetworkPlan:
    """Compute (once per (config, batch)) the fused wave's launch plan.

    ``cfg`` is a frozen ``NetworkConfig`` — hashable, so the cache key is
    the config itself; the plan replaces the per-stage padding recomputation
    the per-layer path does on every kernel wrapper call.

    ``block_b=None`` (the default) consults the autotuner's checked-in
    block cache for this exact geometry (``repro.kernels.autotune``,
    DESIGN.md §14) and falls back to the static defaults (block_b=64,
    8-aligned p1) when the geometry has no tuned entry; an explicit
    ``block_b`` bypasses the cache.

    ``n_cols`` overrides the config's site count with the caller's LOCAL
    site extent — how a model-sharded step (DESIGN.md §16) launches the
    megakernel over just its slice of the column fabric: the grid's site
    dimension comes from the plan, every per-site constant is site-
    invariant, and sites never interact inside a wave, so a local plan is
    the global plan restricted to the shard's rows."""
    if not fused_wave_capable(cfg):
        l_desc = [(l.n_cols, l.column.p, l.column.q) for l in cfg.layers]
        raise ValueError(
            f"network {l_desc} is not fused-wave capable: need same-site "
            f"layers chained so each fan-in equals the previous layer's "
            f"neuron count, a shared WaveSpec, every q <= 128 and padded "
            f"p1 <= {MAX_FUSED_P1}")
    first = cfg.layers[0]
    spec = first.column.wave
    if spec.T >= 255:
        raise ValueError(
            f"wave spec T={spec.T} overflows the packed uint8 spike-time "
            f"encoding: times live in [0, T] with T as the 'no spike' pad "
            f"code, so the data plane requires T <= 254 (DESIGN.md §14) — "
            f"use time_bits <= 7")
    packed = bool(getattr(cfg, "packed", False))
    p_align = 8
    if block_b is None:
        from repro.kernels import autotune as _autotune

        tuned = _autotune.lookup(plan_geometry_key(cfg, batch, n_cols))
        if tuned is not None:
            block_b, p_align = tuned
        else:
            block_b = 64
    pad = PadPlan.make(batch, first.column.p, block_b=block_b,
                       block_p=MAX_FUSED_P1, p_align=p_align,
                       interpret=interpret)
    return NetworkPlan(
        n_cols=first.n_cols if n_cols is None else n_cols,
        ps=tuple(l.column.p for l in cfg.layers),
        qs=tuple(l.column.q for l in cfg.layers),
        thetas=tuple(l.column.theta for l in cfg.layers),
        T=spec.T, w_max=spec.w_max,
        pad=pad,
        tables=tuple(l.column.stdp.table_tuple(spec) for l in cfg.layers),
        mus=tuple((l.column.stdp.mu_capture, l.column.stdp.mu_backoff,
                   l.column.stdp.mu_search) for l in cfg.layers),
        packed=packed,
    )
