"""Multi-layer TNNs — the paper's 2-layer MNIST prototype and arbitrary
N-layer cascades of the same column fabric.

Fig. 19: layer 1 = 625 columns of 32x12 (4x4-pixel on/off receptive fields,
25x25 sites), layer 2 = 625 columns of 12x10 (same-site, fed by layer 1's
12 neurons). 13,750 neurons / 315,000 synapses total. Unsupervised STDP
throughout; classification = per-site winner labelling + majority vote.
Depth is a free design parameter (the TNN design-framework follow-ups treat
it as such): every entry point here — forward, train wave, counter-form
train step, params tree — is depth-agnostic, and ``impl="fused"`` runs any
fused-capable cascade as ONE kernel launch per gamma wave (DESIGN.md §11;
``configs.tnn_mnist.deep_config`` builds N-layer configs).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.column import ColumnConfig
from repro.core.layer import (
    LayerConfig,
    encode_patches_onoff,
    extract_patches,
    init_layer,
    layer_forward,
    layer_stdp_net,
    layer_step,
    layer_uniforms,
)
from repro.core.stdp import STDPConfig, apply_net
from repro.core.temporal import SPIKE_DTYPE, WaveSpec
from repro.kernels import padding as _kpad
from repro.kernels import tnn_wave as _ktw
from repro.utils import tracing


@dataclasses.dataclass(frozen=True)
class NetworkConfig:
    layers: Tuple[LayerConfig, ...]
    image_hw: Tuple[int, int] = (28, 28)
    patch_k: int = 4
    n_classes: int = 10
    # Bit-packed kernel IO for the fused wave executor (DESIGN.md §14):
    # spike volleys cross the pallas_call boundary as uint8 and weights as
    # int8, widening to i32 only inside the kernel accumulator. False keeps
    # the i32-at-the-boundary layout — the two are bit-exact and both
    # compile for a v5e, so the flag is a pure bytes/performance knob and is
    # deliberately excluded from the checkpoint config fingerprint.
    packed: bool = True
    # The front end. ``None``: images, DoG contrast and on/off ``patch_k``
    # patches over ``image_hw``, one site per patch. An int L: a 1-D series
    # of L samples, one synapse line per sample into a single site — the
    # time-series clustering column (arXiv 2102.09200). :func:`encode`
    # picks the encoder from it.
    series_len: Optional[int] = None

    def validate(self) -> None:
        for l in self.layers:
            l.validate()

    @property
    def n_neurons(self) -> int:
        return sum(l.n_neurons for l in self.layers)

    @property
    def n_synapses(self) -> int:
        return sum(l.n_synapses for l in self.layers)


def prototype_config(
    wave: WaveSpec = WaveSpec(),
    stdp: STDPConfig = STDPConfig(),
    sites: int = 625,
    theta1: int = 24,
    theta2: int = 8,
) -> NetworkConfig:
    """The paper's 2-layer prototype (set ``sites`` small for smoke tests)."""
    l1 = LayerConfig(sites, ColumnConfig(p=32, q=12, theta=theta1, wave=wave, stdp=stdp))
    l2 = LayerConfig(sites, ColumnConfig(p=12, q=10, theta=theta2, wave=wave, stdp=stdp))
    return NetworkConfig(layers=(l1, l2))


def with_impl(cfg: NetworkConfig, impl: str) -> NetworkConfig:
    """Rebind every layer's execution backend
    ("direct"/"matmul"/"pallas"/"fused").

    Params and semantics are backend-invariant, so the same weights can be
    trained on one backend and served on another; this is the single switch
    examples/benchmarks/serving flip to route the whole network through
    ``repro.kernels``. "fused" selects the whole-network single-launch wave
    executor when the topology allows it (DESIGN.md §10) and degrades to
    per-layer "pallas" launches otherwise.
    """
    layers = tuple(
        dataclasses.replace(l, column=dataclasses.replace(l.column, impl=impl))
        for l in cfg.layers
    )
    out = dataclasses.replace(cfg, layers=layers)
    out.validate()
    return out


def init_network(rng: jax.Array, cfg: NetworkConfig) -> List[jax.Array]:
    keys = jax.random.split(rng, len(cfg.layers))
    return [init_layer(k, l) for k, l in zip(keys, cfg.layers)]


def dog_filter(images01: jax.Array) -> jax.Array:
    """Center-surround (DoG-style) contrast: pixel minus 3x3 neighborhood
    mean. Flat regions -> ~0 -> NO spikes in either polarity channel — the
    sparse retina-like code the paper's front end assumes."""
    x = images01
    pad = jnp.pad(x, ((0, 0), (1, 1), (1, 1)), mode="edge")
    surround = jnp.zeros_like(x)
    for dr in (-1, 0, 1):
        for dc in (-1, 0, 1):
            surround = surround + pad[:, 1 + dr : 1 + dr + x.shape[1],
                                      1 + dc : 1 + dc + x.shape[2]]
    surround = surround / 9.0
    return x - surround


def input_wave_spec(cfg: NetworkConfig) -> WaveSpec:
    """The wave spec the encoder must encode against — validated, not
    silently ``cfg.layers[0]``: the encoder's time base is consumed by the
    whole cascade (the readout reads ``layers[-1]`` with the same T), so a
    network whose layers disagree on the spec has no well-defined encoding
    and must be rejected up front rather than mis-encoded."""
    specs = [l.column.wave for l in cfg.layers]
    if any(s != specs[0] for s in specs):
        raise ValueError(
            f"encode_images needs one wave spec across the cascade, but the "
            f"layers disagree: {[(s.T, s.w_max) for s in specs]} — encoding "
            f"against layers[0] would silently mis-time every deeper layer")
    first = cfg.layers[0]
    if cfg.series_len is not None:
        if first.n_cols != 1 or first.column.p != cfg.series_len:
            raise ValueError(
                f"a series front end of {cfg.series_len} samples feeds one "
                f"site of {cfg.series_len} synapses, but the input-facing "
                f"layer has {first.n_cols} sites of fan-in {first.column.p}")
        return specs[0]
    p_in = 2 * cfg.patch_k ** 2
    if first.column.p != p_in:
        raise ValueError(
            f"input-facing layer expects fan-in {first.column.p}, "
            f"but a patch_k={cfg.patch_k} on/off front end produces "
            f"{p_in} synapses per site")
    return specs[0]


def encode_images(images01: jax.Array, cfg: NetworkConfig) -> jax.Array:
    """(B, H, W) float in [0,1] -> (B, sites, 32) uint8 spike times.

    DoG contrast -> on/off half-wave rectification -> temporal encoding.
    Strong contrast spikes early; zero contrast never spikes. The wave spec
    is validated against the whole cascade (:func:`input_wave_spec`)."""
    wave = input_wave_spec(cfg)
    with jax.named_scope(tracing.ENCODE):
        c = dog_filter(images01) * 3.0  # contrast gain
        on = extract_patches(jnp.clip(c, 0.0, 1.0), cfg.patch_k)
        off = extract_patches(jnp.clip(-c, 0.0, 1.0), cfg.patch_k)
        t_on = jnp.round((1.0 - on) * wave.T)
        t_off = jnp.round((1.0 - off) * wave.T)
        out = jnp.stack([t_on, t_off], axis=-1).reshape(
            on.shape[0], on.shape[1], on.shape[2] * 2)
        return out.astype(SPIKE_DTYPE)


# The series front end's levels, in standard deviations of a z-normalised
# series: a sample spikes once |x| reaches SERIES_FLOOR, one tick earlier
# for each SERIES_STEP further out. Both are exact in bfloat16 and float32.
SERIES_FLOOR = 1.0
SERIES_STEP = 0.25


def encode_series(series: jax.Array, cfg: NetworkConfig) -> jax.Array:
    """(B, L) z-normalised float32 series -> (B, 1, L) uint8 spike times.

    A sample spikes the earlier the further it lies from its series' mean,
    above or below: at the tick t that counts the levels SERIES_FLOOR +
    k SERIES_STEP (k = 0 .. T-1) lying above |x|. So |x| >= SERIES_FLOOR +
    (T-1) SERIES_STEP spikes at 0 and |x| < SERIES_FLOOR never spikes (time
    T): a series' peaks and troughs are its spikes, the samples near its
    mean are silent. Only compares of |x| against constants, with no
    arithmetic that a backend could round differently."""
    T = input_wave_spec(cfg).T
    with jax.named_scope(tracing.ENCODE):
        mag = jnp.abs(series.astype(jnp.float32))
        t = sum((mag < jnp.float32(SERIES_FLOOR + (T - 1 - k) * SERIES_STEP)
                 ).astype(jnp.int32) for k in range(T))
        return t[:, None, :].astype(SPIKE_DTYPE)


def encode(x: jax.Array, cfg: NetworkConfig) -> jax.Array:
    """The network's front end: :func:`encode_series` for a series config,
    :func:`encode_images` otherwise."""
    if cfg.series_len is not None:
        return encode_series(x, cfg)
    return encode_images(x, cfg)


def _uses_fused_wave(cfg: NetworkConfig) -> bool:
    """True when the network should run as ONE megakernel launch per gamma
    wave: every layer selects ``impl="fused"`` AND the topology matches the
    executor (an N-layer chain of same-site layers, shared wave spec —
    DESIGN.md §10, §11). Fused-but-incapable networks fall through to the
    per-layer path, where each "fused" layer executes as a "pallas"
    launch."""
    return (all(l.column.impl == "fused" for l in cfg.layers)
            and _kpad.fused_wave_capable(cfg))


def _fused_stdp_ready(cfg: NetworkConfig) -> bool:
    """The wave executor's STDP epilogue implements the batched-sum counter
    form only; "seq"/"gauss" reduce modes keep the per-layer path."""
    return all(l.column.stdp.batch_reduce == "sum" for l in cfg.layers)


def network_forward(
    x: jax.Array, params: Sequence[jax.Array], cfg: NetworkConfig
) -> List[jax.Array]:
    """Run all layers; returns per-layer post-WTA spike times.

    The site extent is read from ``x`` (not the config): inside a
    model-sharded ``shard_map`` (DESIGN.md §16) the call sees its LOCAL
    site slice and the fused plan launches over exactly those columns —
    unsharded, ``x.shape[1]`` IS the config's site count."""
    if _uses_fused_wave(cfg):
        plan = _kpad.network_plan(cfg, x.shape[0], n_cols=x.shape[1])
        zs = _ktw.wave_forward(x, tuple(params), plan=plan)
        return [z.astype(SPIKE_DTYPE) for z in zs]
    outs = []
    for w, lcfg in zip(params, cfg.layers):
        x = layer_forward(x, w, lcfg)
        outs.append(x)
    return outs


def network_train_wave(
    x: jax.Array,
    params: Sequence[jax.Array],
    cfg: NetworkConfig,
    rng: jax.Array,
) -> Tuple[List[jax.Array], List[jax.Array]]:
    """One unsupervised gamma wave through the whole network (all layers learn)."""
    with jax.named_scope(tracing.UNIFORMS):
        keys = list(jax.random.split(rng, len(cfg.layers)))
    if _uses_fused_wave(cfg) and _fused_stdp_ready(cfg):
        B = x.shape[0]
        plan = _kpad.network_plan(cfg, B)
        with jax.named_scope(tracing.UNIFORMS):
            us = [layer_uniforms(k, lcfg, B)
                  for lcfg, k in zip(cfg.layers, keys)]
            us = tuple((u[:, 0], u[:, 1]) for u in us)
        zs, nets = _ktw.wave_train(x, tuple(params), us, plan=plan)
        return (
            [z.astype(SPIKE_DTYPE) for z in zs],
            [apply_net(w, net, lcfg.column.wave)
             for w, net, lcfg in zip(params, nets, cfg.layers)],
        )
    new_params, outs = [], []
    for w, lcfg, k in zip(params, cfg.layers, keys):
        x, w = layer_step(x, w, lcfg, k, learn=True)
        new_params.append(w)
        outs.append(x)
    return outs, new_params


# ---------------------------------------------------------------------------
# On-device K-wave scan loop: superbatches of gamma waves (§13).
# ---------------------------------------------------------------------------


def network_forward_superbatch(
    x_k: jax.Array, params: Sequence[jax.Array], cfg: NetworkConfig
) -> List[jax.Array]:
    """Run K forward gamma waves in ONE ``lax.scan`` — x_k is (K, B, C, p)
    encoded spike times, returns per-layer post-WTA spike times stacked on a
    leading wave axis ((K, B, C, q_i) each). Each wave is exactly
    :func:`network_forward` of the matching slice, so classify-per-wave over
    the stacked output matches per-wave classify bit for bit (DESIGN.md
    §13). Under ``impl="fused"`` the scan body holds ONE ``pallas_call``:
    the whole superbatch is one launch geometry per dispatch."""

    def body(carry, x):
        return carry, tuple(network_forward(x, params, cfg))

    _, outs = jax.lax.scan(body, None, x_k)
    return [z for z in outs]


def network_train_superbatch(
    x_k: jax.Array,
    params: Sequence[jax.Array],
    cfg: NetworkConfig,
    keys_k: jax.Array,
    *,
    axis_name: Optional[str] = None,
    data_shards: int = 1,
    model_axis: Optional[str] = None,
    model_shards: int = 1,
) -> Tuple[List[jax.Array], List[jax.Array]]:
    """K consecutive learning gamma waves in ONE ``lax.scan``: the STDP-
    updated weights stay on device between waves (the scan carry), each wave
    ``i`` consumes its own pre-split key ``keys_k[i]`` and is bit-exact with
    one :func:`network_train_wave` / :func:`network_train_step` call on the
    same ``(x, key)`` — so ``scan(K)`` training equals K sequential wave
    steps at any depth and on any backend (DESIGN.md §13).

    x_k: (K, B, C, p) spike times; keys_k: (K,) stacked PRNG keys. The
    counters inside each wave keep the shard-additive ``out="net"`` form
    and psum over ``axis_name`` exactly like the single-wave step, and the
    site axis shards over ``model_axis`` exactly like the single-wave step
    (DESIGN.md §16) — the 2-D sharded training path is one scan over the
    2-D sharded wave. Returns (per-layer z stacks ((K, B, C, q_i) each),
    final per-layer weights)."""

    def body(ps, xs):
        x, key = xs
        outs, new_ps = network_train_step(
            x, list(ps), cfg, key,
            axis_name=axis_name, data_shards=data_shards,
            model_axis=model_axis, model_shards=model_shards)
        return tuple(new_ps), tuple(outs)

    new_params, outs = jax.lax.scan(body, tuple(params), (x_k, keys_k))
    return [z for z in outs], list(new_params)


def superbatch_keys(rng: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Pre-split K per-wave step keys from ONE stream key by the same
    chained ``jax.random.split`` the sequential trainer performs — wave i's
    key is ``split(...split(split(rng)[0])[0]...)[1]`` — so a K-wave
    superbatch consumes exactly the key sequence K single-wave steps would,
    and the stream key that comes back is the one a sequential run would
    carry. This is what makes checkpoint resume K-agnostic (DESIGN.md §13).
    Returns ``(advanced stream key, (K,) stacked per-wave keys)``."""

    def body(key, _):
        key, sub = jax.random.split(key)
        return key, sub

    with jax.named_scope(tracing.UNIFORMS):
        return jax.lax.scan(body, rng, None, length=k)


def network_mesh_spec(cfg: NetworkConfig, mesh) -> _kpad.MeshSpec:
    """THE sharding contract for every step factory and the serving engine
    (DESIGN.md §16): read the (data, model) factorization off ``mesh``
    (either axis may be absent; ``None`` = unsharded) and bind it to the
    config's site count. Model-axis sharding slices the column fabric, so
    it requires one site count across the cascade — heterogeneous-site
    networks must keep the model axis at 1."""
    spec = _kpad.MeshSpec.from_mesh(mesh, cfg.layers[0].n_cols)
    if spec.n_model > 1:
        cols = {l.n_cols for l in cfg.layers}
        if len(cols) != 1:
            raise ValueError(
                f"model-axis sharding slices the site/column axis and needs "
                f"one site count across the cascade, got {sorted(cols)} — "
                f"serve heterogeneous-site networks with model=1")
    return spec


def _site_pad_wrap(inner, spec: _kpad.MeshSpec, T: int, *, x_axis: int,
                   n_leading_replicated: int = 0):
    """Wrap a shard_map'd step whose site extent must divide the model
    axis: pad the site axes of every input with the no-op encodings
    (spikes = ``T``, weights = 0) OUTSIDE the shard_map but INSIDE the
    jit, and slice the pad sites back off every output — pad sites start
    no ramps, win no WTA and fire no STDP case, so their weights stay 0
    and the pad/slice is bit-lossless (DESIGN.md §16). ``inner`` takes
    ``n_leading_replicated`` serve-params args, then (state, x); it
    returns (state, z). Only built when ``spec.site_pad > 0`` — the
    divisible case keeps the bare shard_map (and its donation)."""

    def step(*args):
        serve, (state, x) = args[:n_leading_replicated], args[-2:]
        serve = tuple(spec.pad_weights(list(ps)) for ps in serve)
        state = dict(state, params=spec.pad_params_tree(state["params"]))
        x = spec.pad_spike_sites(x, T, axis=x_axis)
        new_state, z = inner(*serve, state, x)
        new_state = dict(new_state,
                         params=spec.slice_params_tree(new_state["params"]))
        return new_state, spec.slice_sites(z, axis=x_axis)

    return step


def make_superbatch_step(cfg: NetworkConfig, mesh=None, donate: bool = True):
    """Build the jitted K-wave production train step:
    ``(state, x_k) -> (state, z_k)`` — the superbatch form of
    :func:`make_train_step` (DESIGN.md §13).

    ``x_k`` is (K, B, C, p); K is read from the shape, so one returned
    callable serves every chunk size (each distinct K compiles once). The
    state buffers are **donated** — the K STDP weight updates happen in
    place on device with no host round-trip between waves — the per-wave
    keys are pre-split from ``state["rng"]`` by :func:`superbatch_keys`
    (bit-exact with K sequential :func:`make_train_step` calls, so a
    trainer may checkpoint under one ``superbatch_k`` and resume under
    another), and the wave counter advances by K. ``z_k`` stacks the last
    layer's post-WTA spike times per wave ((K, B, C, q)).

    With a ``mesh`` the per-wave batch axis (axis 1) shards over "data"
    and the site axis (axis 2) over "model" per :func:`network_mesh_spec`,
    with the counters psum'd inside the scan body — same bits as the
    unsharded superbatch and as K sequential sharded steps under ANY
    (data, model) factorization (DESIGN.md §16).
    """
    for l in cfg.layers:
        if l.column.stdp.batch_reduce != "sum":
            raise ValueError("make_superbatch_step requires "
                             "batch_reduce='sum'")

    spec = network_mesh_spec(cfg, mesh)

    def step(state, x_k):
        k = x_k.shape[0]
        params = params_from_tree(
            state["params"], cfg,
            n_cols=x_k.shape[2] if spec.n_model > 1 else None)
        key, subs = superbatch_keys(state["rng"], k)
        outs, new_params = network_train_superbatch(
            x_k, params, cfg, subs,
            axis_name=spec.data_axis, data_shards=spec.n_data,
            model_axis=spec.model_axis, model_shards=spec.n_model,
        )
        new_state = {
            "params": params_to_tree(new_params),
            "rng": key,
            "wave": state["wave"] + k,
        }
        return new_state, outs[-1]

    if mesh is not None:
        from repro.sharding import shard_map

        step = shard_map(
            step, mesh=mesh,
            in_specs=(spec.state_spec(), spec.x_spec(leading=1)),
            out_specs=(spec.state_spec(), spec.x_spec(leading=1)),
        )
        if spec.site_pad:
            step = _site_pad_wrap(step, spec, cfg.layers[0].column.wave.T,
                                  x_axis=2)
    donate_args = (0,) if donate and not spec.site_pad else ()
    return jax.jit(step, donate_argnums=donate_args)


# ---------------------------------------------------------------------------
# Production training step: counter-form STDP, shardable, donated (§9).
# ---------------------------------------------------------------------------


def params_to_tree(params: Sequence[jax.Array]) -> Dict[str, jax.Array]:
    """Weight list -> named pytree ({"layer_00": w0, ...}) with stable leaf
    paths — the export form checkpoints and serving warm-starts use."""
    return {f"layer_{i:02d}": w for i, w in enumerate(params)}


def params_from_tree(
    tree: Dict[str, jax.Array], cfg: NetworkConfig,
    n_cols: Optional[int] = None,
) -> List[jax.Array]:
    """Inverse of :func:`params_to_tree`; validates per-layer shapes.
    ``n_cols`` overrides the expected site extent — inside a model-sharded
    ``shard_map`` (DESIGN.md §16) each shard holds a LOCAL site slice of
    every layer's weights, so the leading axis is smaller than the
    config's global count."""
    params = []
    for i, lcfg in enumerate(cfg.layers):
        key = f"layer_{i:02d}"
        if key not in tree:
            raise KeyError(f"params tree missing {key} (have {sorted(tree)})")
        w = tree[key]
        want = (lcfg.n_cols if n_cols is None else n_cols,
                lcfg.column.p, lcfg.column.q)
        if tuple(w.shape) != want:
            raise ValueError(f"{key}: shape {tuple(w.shape)} != {want}")
        params.append(w)
    return params


def network_train_step(
    x: jax.Array,
    params: Sequence[jax.Array],
    cfg: NetworkConfig,
    rng: jax.Array,
    *,
    axis_name: Optional[str] = None,
    data_shards: int = 1,
    model_axis: Optional[str] = None,
    model_shards: int = 1,
) -> Tuple[List[jax.Array], List[jax.Array]]:
    """One gamma wave of online STDP — the counter-form of
    :func:`network_train_wave`, bit-exact with it and 2-D shardable.

    x: (b, C_loc, p) spike times — the local batch rows / site columns when
    running inside a ``shard_map`` over ``axis_name`` (batch over "data")
    and/or ``model_axis`` (sites over "model"), the full extents otherwise.
    Every shard draws the STDP uniforms for the GLOBAL batch
    (``b * data_shards`` rows) and GLOBAL site count from the same
    per-layer/per-column key split, pads the site axis with the no-op 1.0
    up to the model-axis multiple, and slices out its own sites and rows —
    then computes local net counters and psums them over ``axis_name``
    before one saturating apply. The cascade is same-site (WTA is
    column-local, layer i feeds layer i+1 AT THE SAME SITE), so the model
    axis needs no collective at all: per-site counters are complete on
    their shard, and only the batch-partial sums cross the wire. The
    trained weights are therefore invariant to the full (data, model)
    factorization (DESIGN.md §9, §16). Requires
    ``STDPConfig.batch_reduce == "sum"``.

    Returns (per-layer post-WTA spike times, new per-layer weights).
    """
    b_local = x.shape[0]
    B = b_local * data_shards
    c_local = x.shape[1]
    row0 = 0 if axis_name is None else jax.lax.axis_index(axis_name) * b_local
    site0 = (0 if model_axis is None
             else jax.lax.axis_index(model_axis) * c_local)

    def draw(key, lcfg):
        # this shard's (c_local, 2, b_local, p, q) slice of the layer's
        # (C_global, 2, B, p, q) global draws, split into up and down. Site
        # axis first (pad with the no-op 1.0 so the model multiple
        # divides), then batch rows.
        with jax.named_scope(tracing.UNIFORMS):
            u = layer_uniforms(key, lcfg, B)
            if model_axis is not None:
                u = _kpad.pad_uniform_sites(u, c_local * model_shards)
                u = jax.lax.dynamic_slice_in_dim(u, site0, c_local, axis=0)
            u = jax.lax.dynamic_slice_in_dim(u, row0, b_local, axis=2)
            return u[:, 0], u[:, 1]

    def psum(net):
        if axis_name is None:
            return net
        with jax.named_scope(tracing.PSUM):
            return jax.lax.psum(net, axis_name)

    with jax.named_scope(tracing.UNIFORMS):
        keys = list(jax.random.split(rng, len(cfg.layers)))
    if _uses_fused_wave(cfg) and _fused_stdp_ready(cfg):
        # One megakernel launch for the whole wave, any depth (DESIGN.md
        # §10, §11), gridded over the LOCAL site slice. The uniforms are
        # still drawn for the GLOBAL extents from the same per-layer/
        # per-column key split and sliced per shard, and the counters
        # still psum — bits identical to the per-layer path.
        plan = _kpad.network_plan(cfg, b_local, n_cols=c_local)
        us = tuple(draw(k, lcfg) for lcfg, k in zip(cfg.layers, keys))
        zs, nets = _ktw.wave_train(x, tuple(params), us, plan=plan)
        nets = [psum(net) for net in nets]
        return (
            [z.astype(SPIKE_DTYPE) for z in zs],
            [apply_net(w, net, lcfg.column.wave)
             for w, net, lcfg in zip(params, nets, cfg.layers)],
        )
    new_params, outs = [], []
    for w, lcfg, k in zip(params, cfg.layers, keys):
        z = layer_forward(x, w, lcfg)
        u_up, u_dn = draw(k, lcfg)  # global draws, local slice
        net = psum(layer_stdp_net(x, z, w, lcfg, u_up, u_dn))
        w = apply_net(w, net, lcfg.column.wave)
        new_params.append(w)
        outs.append(z)
        x = z
    return outs, new_params


def make_train_step(cfg: NetworkConfig, mesh=None, donate: bool = True):
    """Build the jitted production train step: ``(state, x) -> (state, z)``.

    ``state`` is the training pytree ``{"params": {"layer_00": ...}, "rng":
    key, "wave": i32}``; ``x`` is one encoded wave batch (B, C, p) int8. The
    returned ``z`` is the last layer's post-WTA spike times (for metrics /
    vote-table building). The state argument's buffers are donated, so the
    weight update happens in place on device — callers must keep only the
    returned state (the trainer checkpoints by materializing to host first).

    With a ``mesh`` the batch axis shards over "data" and the site axis
    over "model" per :func:`network_mesh_spec` (DESIGN.md §9, §16):
    params site-sharded over "model" (rng/wave replicated), x and z on
    (data, model), STDP counters psum'd over "data" — same bits as the
    unsharded step under ANY (data, model) factorization. B must divide
    by the data axis size; a site count that does not divide the model
    axis is padded with no-op sites outside the shard_map.
    """
    for l in cfg.layers:
        if l.column.stdp.batch_reduce != "sum":
            raise ValueError("make_train_step requires batch_reduce='sum'")

    spec = network_mesh_spec(cfg, mesh)

    def step(state, x):
        params = params_from_tree(
            state["params"], cfg,
            n_cols=x.shape[1] if spec.n_model > 1 else None)
        with jax.named_scope(tracing.UNIFORMS):
            key, sub = jax.random.split(state["rng"])
        outs, new_params = network_train_step(
            x, params, cfg, sub,
            axis_name=spec.data_axis, data_shards=spec.n_data,
            model_axis=spec.model_axis, model_shards=spec.n_model,
        )
        new_state = {
            "params": params_to_tree(new_params),
            "rng": key,
            "wave": state["wave"] + 1,
        }
        return new_state, outs[-1]

    if mesh is not None:
        from repro.sharding import shard_map

        step = shard_map(
            step, mesh=mesh,
            in_specs=(spec.state_spec(), spec.x_spec()),
            out_specs=(spec.state_spec(), spec.x_spec()),
        )
        if spec.site_pad:
            step = _site_pad_wrap(step, spec, cfg.layers[0].column.wave.T,
                                  x_axis=1)
    donate_args = (0,) if donate and not spec.site_pad else ()
    return jax.jit(step, donate_argnums=donate_args)


def init_train_state(rng: jax.Array, cfg: NetworkConfig) -> Dict:
    """Fresh training state for :func:`make_train_step`: random weights, a
    forked step key, wave counter 0."""
    k_params, k_stream = jax.random.split(rng)
    return {
        "params": params_to_tree(init_network(k_params, cfg)),
        "rng": k_stream,
        "wave": jnp.asarray(0, jnp.int32),
    }


# ---------------------------------------------------------------------------
# Learn-while-serving: classify under published weights, learn on shadow (§15).
# ---------------------------------------------------------------------------


def make_online_step(cfg: NetworkConfig, mesh=None, donate: bool = True):
    """Build the jitted learn-while-serving step:
    ``(serve_params, state, x) -> (state, z_serve)`` (DESIGN.md §15).

    One gamma wave runs BOTH halves of online mode. The request batch is
    classified by a forward under the PUBLISHED serving weights
    ``serve_params`` (``weights_v`` — read-only inside the step), while
    the same volley drives one :func:`network_train_step` on the shadow
    training state (``weights_v+1``). The shadow half is byte-for-byte
    the :func:`make_train_step` body — same ``rng`` split, same
    counter-form STDP with the psum over ``axis_name``, same wave-counter
    advance — so N online-served learning waves produce bit-identical
    shadow weights to N trainer steps on the same volley stream
    (``tests/test_online_serving.py`` asserts it per backend and under a
    sharded mesh). Pad rows (spike time T everywhere) fire no synapse and
    no neuron, so every STDP case plane is False for them: partial waves
    are learning-inert beyond their real rows, and serving's no-op
    padding never perturbs the shadow stream.

    The ``state`` buffers are donated (the weight update happens in
    place); ``serve_params`` is NOT — it keeps serving until the next hot
    swap publishes the shadow — so callers must never alias the two.
    """
    for l in cfg.layers:
        if l.column.stdp.batch_reduce != "sum":
            raise ValueError("make_online_step requires batch_reduce='sum'")

    spec = network_mesh_spec(cfg, mesh)

    def step(serve_params, state, x):
        params = params_from_tree(
            state["params"], cfg,
            n_cols=x.shape[1] if spec.n_model > 1 else None)
        with jax.named_scope(tracing.UNIFORMS):
            key, sub = jax.random.split(state["rng"])
        _, new_params = network_train_step(
            x, params, cfg, sub,
            axis_name=spec.data_axis, data_shards=spec.n_data,
            model_axis=spec.model_axis, model_shards=spec.n_model,
        )
        z = network_forward(x, list(serve_params), cfg)[-1]
        new_state = {
            "params": params_to_tree(new_params),
            "rng": key,
            "wave": state["wave"] + 1,
        }
        return new_state, z

    if mesh is not None:
        from repro.sharding import shard_map

        step = shard_map(
            step, mesh=mesh,
            in_specs=(spec.params_spec(), spec.state_spec(), spec.x_spec()),
            out_specs=(spec.state_spec(), spec.x_spec()),
        )
        if spec.site_pad:
            step = _site_pad_wrap(step, spec, cfg.layers[0].column.wave.T,
                                  x_axis=1, n_leading_replicated=1)
    donate_args = (1,) if donate and not spec.site_pad else ()
    return jax.jit(step, donate_argnums=donate_args)


def make_online_superbatch_step(cfg: NetworkConfig, mesh=None,
                                donate: bool = True):
    """The K-wave form of :func:`make_online_step`:
    ``(serve_params, state, x_k) -> (state, z_k)`` with ``x_k`` shaped
    (K, B, C, p) — one jitted dispatch classifies K admitted waves under
    the published weights (``lax.scan``, DESIGN.md §13) while the shadow
    state learns through :func:`network_train_superbatch` with the same
    :func:`superbatch_keys` pre-split the trainer uses, so online
    superbatch learning stays bit-exact with K sequential online steps —
    and therefore with the trainer at any ``superbatch_k``."""
    for l in cfg.layers:
        if l.column.stdp.batch_reduce != "sum":
            raise ValueError("make_online_superbatch_step requires "
                             "batch_reduce='sum'")

    spec = network_mesh_spec(cfg, mesh)

    def step(serve_params, state, x_k):
        k = x_k.shape[0]
        params = params_from_tree(
            state["params"], cfg,
            n_cols=x_k.shape[2] if spec.n_model > 1 else None)
        key, subs = superbatch_keys(state["rng"], k)
        _, new_params = network_train_superbatch(
            x_k, params, cfg, subs,
            axis_name=spec.data_axis, data_shards=spec.n_data,
            model_axis=spec.model_axis, model_shards=spec.n_model,
        )
        z_k = network_forward_superbatch(x_k, list(serve_params), cfg)[-1]
        new_state = {
            "params": params_to_tree(new_params),
            "rng": key,
            "wave": state["wave"] + k,
        }
        return new_state, z_k

    if mesh is not None:
        from repro.sharding import shard_map

        step = shard_map(
            step, mesh=mesh,
            in_specs=(spec.params_spec(), spec.state_spec(),
                      spec.x_spec(leading=1)),
            out_specs=(spec.state_spec(), spec.x_spec(leading=1)),
        )
        if spec.site_pad:
            step = _site_pad_wrap(step, spec, cfg.layers[0].column.wave.T,
                                  x_axis=2, n_leading_replicated=1)
    donate_args = (1,) if donate and not spec.site_pad else ()
    return jax.jit(step, donate_argnums=donate_args)


def forward_all_padded(forward_fn, params, x, batch: int, T: int) -> jax.Array:
    """Chunked fixed-shape forward over any number of encoded rows.

    Slices ``x`` ((N, C, p) spike times) into ``batch``-row chunks, pads
    the ragged tail with the shared no-op encoding (spike time ``T`` —
    the SAME convention serving's admission path uses) and concatenates
    the last layer's post-WTA times back to (N, C, q). ``forward_fn`` is
    a jitted ``(params, x) -> z`` — the trainer's and the engine's
    forwards both fit, which is what makes the labelling pass one shared
    code path (DESIGN.md §15)."""
    outs = []
    for off in range(0, x.shape[0], batch):
        chunk = jnp.asarray(x[off:off + batch])
        k = chunk.shape[0]
        chunk = _kpad.pad_batch_rows(chunk, batch, T)
        outs.append(forward_fn(params, chunk)[:k])
    return jnp.concatenate(outs, axis=0)


def refresh_vote_table(forward_fn, params, x, labels, cfg: NetworkConfig,
                       batch: int) -> jax.Array:
    """One labelled pass -> fresh vote table for the given weights.

    THE vote-table refresh both stacks share: ``TNNTrainer.evaluate``
    rebuilds its readout through this at every eval cadence point, and
    ``TNNEngine`` calls it from ``fit`` and from every online hot swap
    (rebuilding the readout at ``weights_v+1`` before publishing,
    DESIGN.md §15) — so a swap-published vote table is bit-identical to
    the one the trainer would checkpoint for the same weights."""
    T = cfg.layers[-1].column.wave.T
    z = forward_all_padded(forward_fn, params, x, batch, T)
    return build_vote_table(z, jnp.asarray(labels), cfg.n_classes, T)


# ---------------------------------------------------------------------------
# Unsupervised readout: label neurons by the classes they win on, then vote.
# ---------------------------------------------------------------------------


def winner_map(z_last: jax.Array, T: int) -> Tuple[jax.Array, jax.Array]:
    """Per (batch, site): winning neuron index and fired mask. z: (B, S, q)."""
    winner = jnp.argmin(z_last.astype(jnp.int32), axis=-1)
    fired = (z_last.astype(jnp.int32) < T).any(axis=-1)
    return winner, fired


def build_vote_table(
    z_last: jax.Array, labels: jax.Array, n_classes: int, T: int
) -> jax.Array:
    """Histogram (sites, q, n_classes): how often neuron (s, j) wins on class c."""
    B, S, q = z_last.shape
    winner, fired = winner_map(z_last, T)  # (B, S)
    onehot_w = jax.nn.one_hot(winner, q, dtype=jnp.float32) * fired[..., None]
    onehot_c = jax.nn.one_hot(labels, n_classes, dtype=jnp.float32)  # (B, C)
    return jnp.einsum("bsq,bc->sqc", onehot_w, onehot_c)


def classify(z_last: jax.Array, vote_table: jax.Array, T: int,
             soft: bool = True) -> jax.Array:
    """Vote of per-site winner labels. Returns (B,) class ids.

    ``soft=True`` weights each firing site's vote by its empirical class
    posterior P(c | site, winner) — in hardware a small per-neuron LUT
    feeding the vote counters; ``soft=False`` is the plain majority vote of
    argmax site labels."""
    with jax.named_scope(tracing.CLASSIFY):
        winner, fired = winner_map(z_last, T)  # (B, S)
        n_classes = vote_table.shape[-1]
        S = vote_table.shape[0]
        if soft:
            post = vote_table / jnp.maximum(
                vote_table.sum(axis=-1, keepdims=True), 1.0)  # (S, q, C)
            votes = post[jnp.arange(S)[None, :], winner]  # (B, S, C)
            votes = votes * fired[..., None]
            return jnp.argmax(votes.sum(axis=1), axis=-1)
        site_label = jnp.argmax(vote_table, axis=-1)  # (S, q)
        lab = site_label[jnp.arange(S)[None, :], winner]  # (B, S)
        votes = (jax.nn.one_hot(lab, n_classes, dtype=jnp.float32)
                 * fired[..., None])
        return jnp.argmax(votes.sum(axis=1), axis=-1)


def winner_bits(z_last: jax.Array, T: int) -> jax.Array:
    """(B, S, q) post-WTA spike times -> flat binary winner map (B, S*q).
    The sparse code the prototype's readout hardware sees (one bit per
    neuron per gamma wave)."""
    return (z_last.astype(jnp.int32) < T).reshape(z_last.shape[0], -1)


def build_centroids(z_last: jax.Array, labels: jax.Array, n_classes: int,
                    T: int) -> jax.Array:
    """Per-class mean winner-bit vectors (C, S*q) — in hardware: per-class
    counters accumulated during the labelling pass."""
    bits = winner_bits(z_last, T).astype(jnp.float32)
    onehot = jax.nn.one_hot(labels, n_classes, dtype=jnp.float32)  # (B, C)
    sums = jnp.einsum("bf,bc->cf", bits, onehot)
    counts = jnp.maximum(onehot.sum(axis=0), 1.0)
    return sums / counts[:, None]


def classify_centroid(z_last: jax.Array, centroids: jax.Array, T: int) -> jax.Array:
    """Nearest-centroid on winner bits (min distance = max correlation —
    a Hamming-style comparator over the wave's spike pattern)."""
    bits = winner_bits(z_last, T).astype(jnp.float32)
    d = (jnp.square(bits[:, None, :] - centroids[None]).sum(-1))
    return jnp.argmin(d, axis=-1)
