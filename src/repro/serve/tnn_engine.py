"""TNN-as-a-service: continuous-batching wave pipeline over the fused path.

The LM :class:`repro.serve.engine.Engine` amortizes jit cost by giving every
request a *slot* in one fixed-shape batched decode step. Classification with
the TNN prototype is one gamma wave per image, so the same trick collapses
to its simplest form: ``n_slots`` fixed batch rows, one jitted forward per
wave regardless of how many requests are queued, idle rows carried as no-op
spike encodings whose outputs are ignored.

Serving is a **continuous-batching pipeline** (DESIGN.md §12), not a
lock-step loop:

* **Admission queue.** ``submit`` timestamps each request on enqueue and
  appends it to a FIFO; every wave admits up to ``n_slots`` requests.
  Partial batches are padded with the shared no-op encoding
  (:func:`repro.kernels.padding.pad_batch_rows` — spike time ``T``), and a
  tick with an EMPTY queue skips the launch entirely: idle slots never burn
  a wave.
* **Double buffering.** ``poll`` stages and dispatches wave *i+1* (host-side
  image staging + jitted encode + forward + classify, all async under JAX
  dispatch) BEFORE blocking on wave *i*'s classify readout — the only
  ``block_until_ready`` point is the ``np.asarray`` on the (b,) predicted
  class ids, so host staging overlaps device compute.
* **K-wave superbatch drain.** With ``superbatch_k > 1`` a tick whose
  backlog is deeper than one wave admits up to ``K x n_slots`` requests and
  dispatches them as ONE jitted ``lax.scan`` over K gamma waves
  (DESIGN.md §13) — the Python dispatch cost is paid once per K waves, but
  the latency record stays per-REQUEST (each request keeps its own
  enqueue/serve timestamps), and every wave of the superbatch counts in
  ``ServeStats.waves`` exactly like a separately dispatched wave.
* **Latency accounting.** Every request carries enqueue/serve timestamps;
  :meth:`TNNEngine.stats` aggregates them into a :class:`ServeStats` record
  (p50/p95 request latency, waves/sec, images/sec, slot occupancy) — the
  figure of merit ``benchmarks/run.py --serve`` regression-gates.

The forward runs through the network's configured backend — ``"pallas"`` by
default; ``"fused"`` classifies each wave in ONE megakernel launch — and
the mesh factorizes 2-D (DESIGN.md §16): the batch (slot) axis
``shard_map``-shards over the mesh's "data" axis and the site/column axis
over its "model" axis via :mod:`repro.sharding`, so the identical engine
serves from one CPU device (smoke tests, ``interpret=True``), a 1-D data
mesh, or a production ("data", "model") TPU mesh
(``launch/serve.py --arch tnn-mnist --mesh DxM``). Params are site-sharded
over "model"; the vote table stays host-side (classify runs on the
gathered readout); spikes/results travel on (batch, site). Encoding is
per-image elementwise, so staging it host-side before the sharded forward
is bit-identical to encoding inside the shard.

The readout is the paper's unsupervised labelling: :meth:`TNNEngine.fit`
runs one labelled pass to build the per-site vote table (DESIGN.md §1), and
every served request is classified by the soft site vote. A trained
deployment skips ``fit`` entirely: :meth:`TNNEngine.from_checkpoint`
warm-starts weights AND vote table from a TNN training checkpoint
(DESIGN.md §9), so serving picks up exactly where training left off.

**Learn while serving** (``online_stdp=True``, DESIGN.md §15): the paper's
prototype is an *online*-learning sensory processor, so the engine can run
the STDP-counter epilogue on live traffic. Every served wave then executes
``core.network.make_online_step`` — ONE dispatch that classifies the batch
under the published ``weights_v`` AND advances a shadow training state
(``weights_v+1``) with byte-for-byte the trainer's step (same RNG split,
same counter form, psum'd over the mesh) — so the shadow weights stay
bit-exact with ``TNNTrainer`` on the same volley stream. On the
``swap_every`` cadence (or an explicit :meth:`hot_swap`) the engine
rebuilds the vote table at v+1 through the shared
``core.network.refresh_vote_table`` pass, checkpoints shadow state + table
through the crash-safe ``Checkpointer``, and PUBLISHES atomically: params,
vote table and version live in one ``_published`` tuple that every
dispatch snapshots exactly once, so an in-flight wave keeps classifying
against the immutable v arrays while new admissions see v+1 — zero
requests dropped, duplicated, or classified against a half-published
version. Requests record the version they were classified under;
:meth:`TNNEngine.stats_by_version` splits the latency/occupancy record per
version (the A/B surface ``tools/loadgen.py``'s labelled probe reads).

For operators, admission, staging, dispatch, retire and hot swap run in
``tnn.admit``/``tnn.stage``/``tnn.dispatch``/``tnn.retire``/``tnn.hot_swap``
host spans (``utils/tracing.py``; ``launch/serve.py --profile DIR`` records
them), and ``ServeStats.compiles`` counts the executables obtained since
construction or :meth:`TNNEngine.reset`: a warm engine reads 0.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.network import (
    NetworkConfig,
    classify,
    encode,
    init_train_state,
    make_online_step,
    make_online_superbatch_step,
    network_forward,
    network_forward_superbatch,
    network_mesh_spec,
    params_from_tree,
    params_to_tree,
    refresh_vote_table,
    with_impl,
)
from repro.kernels.padding import pad_batch_rows
from repro.sharding import shard_map
from repro.utils import tracing


@dataclasses.dataclass
class ClassifyRequest:
    uid: int
    image: np.ndarray  # (H, W) intensities in [0, 1], or an (L,) series
    result: Optional[int] = None  # class id, filled when served
    t_enqueue: Optional[float] = None  # perf_counter at submit()
    t_done: Optional[float] = None  # perf_counter when the wave retired
    version: Optional[int] = None  # params/vote-table version classified under

    @property
    def latency_s(self) -> Optional[float]:
        """Enqueue-to-serve latency — queueing + staging + wave compute."""
        if self.t_enqueue is None or self.t_done is None:
            return None
        return self.t_done - self.t_enqueue


@dataclasses.dataclass(frozen=True)
class ServeStats:
    """Aggregate serving record (DESIGN.md §12). ``wall_s`` spans first
    dispatch to last retire; occupancy is served rows over offered slot
    rows (``waves * n_slots``) — 1.0 means every wave ran full.
    ``compiles``: executables obtained since construction or ``reset``
    (``None`` in the per-version split, which does not divide them)."""

    requests: int
    waves: int
    wall_s: float
    waves_per_s: float
    images_per_s: float
    p50_ms: float
    p95_ms: float
    occupancy: float
    compiles: Optional[int] = None


class ServeTimeout(RuntimeError):
    """``run_until_done`` hit ``max_ticks`` with requests outstanding.

    Carries the served/unserved split so callers can account for every
    request instead of discovering a silently partial ``done`` dict.
    ``unserved`` counts BOTH the queued requests and any wave the
    double-buffered ``poll`` staged but had not retired at the limit
    (``in_flight`` gives that slice on its own): the timeout path never
    blocks on a dispatch that may be the very thing hanging, so those
    requests are not in ``done`` yet — they stay in flight and a later
    ``poll``/``run_until_done`` retires them, with ``served + unserved``
    covering every submitted uid at all times."""

    def __init__(self, served: int, unserved: int, max_ticks: int,
                 in_flight: int = 0):
        self.served = served
        self.unserved = unserved
        self.max_ticks = max_ticks
        self.in_flight = in_flight
        super().__init__(
            f"run_until_done hit max_ticks={max_ticks} with {unserved} "
            f"request(s) outstanding ({served} served, {in_flight} of the "
            f"unserved still in flight)")


class TNNEngine:
    """Continuous-batching classification engine for the TNN prototype.

    Args:
        cfg: network config; its backend is overridden by ``impl``.
        params: per-layer weight list (as from ``init_network`` or training).
        n_slots: concurrent images per jitted call (the fixed batch shape).
            Must be a multiple of the mesh's "data" axis size.
        impl: execution backend for serving ("pallas" routes every layer
            through repro.kernels.ops; "fused" classifies each wave in ONE
            megakernel launch via repro.kernels.tnn_wave — at any cascade
            depth, DESIGN.md §10, §11; "direct"/"matmul" are the
            references).
        mesh: optional ``Mesh`` — a "data" axis shards the slot axis, a
            "model" axis shards the site/column axis (either may be
            absent, DESIGN.md §16); ``None`` serves unsharded.
        superbatch_k: max gamma waves one ``poll`` dispatch may scan on
            device when the admission queue is deeper than ``n_slots``
            (DESIGN.md §13); 1 = one wave per dispatch (the PR-5 pipeline).
        online_stdp: learn while serving (DESIGN.md §15) — every served
            wave also drives the STDP epilogue on a shadow training state
            that :meth:`hot_swap` publishes; requests keep classifying
            against the stable published version in between.
        swap_every: learning waves between automatic hot swaps (0 = only
            explicit :meth:`hot_swap` calls publish); needs ``fit`` or
            :meth:`set_label_data` first, since a swap rebuilds the vote
            table at the new weights.
        seed: PRNG seed for the shadow stream when ``online_stdp`` starts
            fresh — matches ``TNNTrainConfig.seed``'s key chain, so an
            engine seeded like a trainer learns the trainer's exact
            stream (``from_checkpoint`` overrides this with the restored
            RNG/wave to continue a trained stream instead).
        ckpt_dir: where hot swaps checkpoint the published state (None =
            swaps skip the checkpoint write).
    """

    def __init__(
        self,
        cfg: NetworkConfig,
        params: Sequence[jax.Array],
        n_slots: int = 8,
        impl: str = "pallas",
        mesh: Optional[Mesh] = None,
        superbatch_k: int = 1,
        online_stdp: bool = False,
        swap_every: int = 0,
        seed: int = 0,
        ckpt_dir: Optional[str] = None,
    ):
        cfg = with_impl(cfg, impl)
        cfg.validate()
        if superbatch_k < 1:
            raise ValueError(f"superbatch_k={superbatch_k} must be >= 1")
        if swap_every < 0:
            raise ValueError(f"swap_every={swap_every} must be >= 0")
        if swap_every and not online_stdp:
            raise ValueError("swap_every needs online_stdp=True — there is "
                             "no shadow state to swap in otherwise")
        if mesh is not None:
            ndata = mesh.shape.get("data", 1)
            if n_slots % max(ndata, 1):
                raise ValueError(f"n_slots={n_slots} not divisible by "
                                 f"data axis size {ndata}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.mesh = mesh
        self.superbatch_k = superbatch_k
        # THE published snapshot (DESIGN.md §15): params, vote table and
        # version move together in one tuple — dispatch reads it exactly
        # once per wave and a hot swap replaces it in one assignment, so
        # no request can ever see v's weights with v+1's vote table.
        self._published: Tuple[List[jax.Array], Optional[jax.Array], int] = (
            list(params), None, 0)
        self.T = cfg.layers[-1].column.wave.T
        self.queue: Deque[ClassifyRequest] = collections.deque()
        self.done: Dict[int, ClassifyRequest] = {}
        self.waves_served = 0
        # one dispatch at most rides in flight: (per-wave admitted request
        # lists, async (k, n_slots) preds) — k == 1 for single-wave ticks
        self._inflight: Optional[
            Tuple[List[List[ClassifyRequest]], jax.Array]] = None
        self._lat_ms: List[float] = []
        self._slots_filled = 0
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        # per-version accounting: version -> [lat_ms...], waves, slots
        self._lat_by_ver: Dict[int, List[float]] = {}
        self._waves_by_ver: Dict[int, int] = {}
        self._slots_by_ver: Dict[int, int] = {}
        self._span_by_ver: Dict[int, Tuple[float, float]] = {}

        # learn-while-serving half (DESIGN.md §15)
        self.online_stdp = online_stdp
        self.swap_every = swap_every
        self.swaps = 0
        self._learn_waves = 0  # learning waves since the last hot swap
        self._label_set: Optional[Tuple[np.ndarray, np.ndarray]] = None
        if ckpt_dir is not None:
            from repro.checkpoint.checkpointer import Checkpointer

            self.ckpt: Optional["Checkpointer"] = Checkpointer(ckpt_dir)
        else:
            self.ckpt = None
        if online_stdp:
            self._online = make_online_step(cfg, mesh=mesh)
            self._online_sb = (make_online_superbatch_step(cfg, mesh=mesh)
                               if superbatch_k > 1 else None)
            # the shadow stream starts AT the served weights with the
            # trainer's key chain; COPIES, never aliases — the online
            # step donates the shadow buffers, the published ones must
            # survive until the next swap
            st = init_train_state(jax.random.PRNGKey(seed), cfg)
            self.learn_state: Optional[Dict] = {
                "params": params_to_tree([jnp.array(w) for w in params]),
                "rng": st["rng"],
                "wave": st["wave"],
            }
        else:
            self._online = self._online_sb = None
            self.learn_state = None

        # Staging half: the jitted encoder runs on the ragged admitted
        # batch (at most n_slots distinct shapes ever compile) so partial
        # waves pad ENCODED spikes with the shared no-op value T instead of
        # inventing a second image-level padding convention.
        self._encode = jax.jit(lambda imgs: encode(imgs, self.cfg))

        def fwd(ps, x):  # (b, S, p) spikes -> (b, S, q) last-layer times
            return network_forward(x, list(ps), self.cfg)[-1]

        def fwd_k(ps, x_k):  # (k, slots, S, p) -> (k, slots, S, q)
            return network_forward_superbatch(x_k, list(ps), self.cfg)[-1]

        if mesh is None:
            self._forward = jax.jit(fwd)
            self._forward_sb = jax.jit(fwd_k)
        else:
            # spec-driven 2-D sharding (DESIGN.md §16): slots over "data",
            # sites over "model" (params site-sharded); a site count that
            # does not divide the model axis rides through no-op pad sites
            # added outside the shard_map and sliced off the readout —
            # classify runs on the gathered logical z, so the site-sum
            # vote never sees a pad site.
            sp = network_mesh_spec(self.cfg, mesh)
            t_in = self.cfg.layers[0].column.wave.T
            inner = shard_map(
                fwd, mesh=mesh,
                in_specs=(sp.params_spec(), sp.x_spec()),
                out_specs=sp.x_spec(),
            )
            inner_k = shard_map(
                fwd_k, mesh=mesh,
                in_specs=(sp.params_spec(), sp.x_spec(leading=1)),
                out_specs=sp.x_spec(leading=1),
            )
            if sp.site_pad:
                def fwd_pad(ps, x):
                    z = inner(sp.pad_weights(list(ps)),
                              sp.pad_spike_sites(x, t_in, axis=1))
                    return sp.slice_sites(z, axis=1)

                def fwd_k_pad(ps, x_k):
                    z_k = inner_k(sp.pad_weights(list(ps)),
                                  sp.pad_spike_sites(x_k, t_in, axis=2))
                    return sp.slice_sites(z_k, axis=2)

                self._forward = jax.jit(fwd_pad)
                self._forward_sb = jax.jit(fwd_k_pad)
            else:
                self._forward = jax.jit(inner)
                self._forward_sb = jax.jit(inner_k)
        self._classify = jax.jit(
            lambda z, vt: classify(z, vt, self.T, soft=True))
        self._compiles0 = tracing.compile_count()

    # -- published snapshot (DESIGN.md §15) --------------------------------

    @property
    def params(self) -> List[jax.Array]:
        """The published serving weights (``weights_v``)."""
        return self._published[0]

    @params.setter
    def params(self, ps: Sequence[jax.Array]) -> None:
        _, vt, ver = self._published
        self._published = (list(ps), vt, ver)

    @property
    def vote_table(self) -> Optional[jax.Array]:
        """The published vote-table readout for ``weights_v``."""
        return self._published[1]

    @vote_table.setter
    def vote_table(self, vt: Optional[jax.Array]) -> None:
        ps, _, ver = self._published
        self._published = (ps, vt, ver)

    @property
    def version(self) -> int:
        """Publish counter: bumped by every :meth:`hot_swap`."""
        return self._published[2]

    @classmethod
    def from_checkpoint(
        cls,
        ckpt_dir: str,
        cfg: NetworkConfig,
        *,
        step: Optional[int] = None,
        n_slots: int = 8,
        impl: str = "pallas",
        mesh: Optional[Mesh] = None,
        superbatch_k: int = 1,
        label_data: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        online_stdp: bool = False,
        swap_every: int = 0,
        swap_ckpt_dir: Optional[str] = None,
    ) -> "TNNEngine":
        """Warm-start serving from a TNN training checkpoint.

        Restores the per-layer weights and — when the trainer has run a
        labelling pass (``extra["has_vote"]``) — the vote table, so the
        engine classifies immediately without a ``fit`` pass. ``step=None``
        takes the latest checkpoint. The checkpoint carries no mesh info,
        so the same files warm-start any serving mesh (DESIGN.md §9).

        A checkpoint written BEFORE any labelling pass has no usable vote
        table (``extra["has_vote"]`` falsy — the stored array is the
        all-zeros placeholder): pass ``label_data=(images, labels)`` to
        rebuild the readout at load through the shared
        ``refresh_vote_table`` pass, otherwise this fails fast here with
        the remedy instead of serving garbage or crashing later.

        With ``online_stdp=True`` the shadow stream CONTINUES the
        trainer's: the restored RNG key and wave counter seed the shadow
        state, so N more online-served learning waves equal the trainer
        resuming for N waves on the same stream (DESIGN.md §15). Swap
        checkpoints go back to ``ckpt_dir`` (override: ``swap_ckpt_dir``)
        — serve, learn, swap, restart, and the next warm start picks up
        the adapted weights.
        """
        from repro.checkpoint.checkpointer import Checkpointer, restore_tnn

        state, extra = restore_tnn(Checkpointer(ckpt_dir), cfg, step)
        eng = cls(cfg, params_from_tree(state["params"], cfg),
                  n_slots=n_slots, impl=impl, mesh=mesh,
                  superbatch_k=superbatch_k, online_stdp=online_stdp,
                  swap_every=swap_every,
                  ckpt_dir=(swap_ckpt_dir or ckpt_dir) if online_stdp
                  else swap_ckpt_dir)
        if online_stdp:
            eng.learn_state = {
                "params": params_to_tree(
                    [jnp.array(w) for w in eng.params]),
                "rng": jnp.asarray(state["rng"]),
                "wave": jnp.asarray(state["wave"]),
            }
        if label_data is not None:
            eng.set_label_data(*label_data)
        if extra.get("has_vote"):
            eng.vote_table = state["vote_table"]
        elif label_data is not None:
            x, labs = eng._label_set
            eng.vote_table = refresh_vote_table(
                eng._forward, eng.params, x, labs, cfg, n_slots)
        else:
            raise ValueError(
                f"checkpoint step {extra.get('wave', step)} under "
                f"{ckpt_dir!r} has no vote table (extra['has_vote'] is "
                f"falsy — the trainer checkpointed before any labelling "
                f"pass, so the stored table is the all-zeros placeholder "
                f"and every classify would be meaningless). Pass "
                f"label_data=(images, labels) to rebuild the readout at "
                f"load, or warm-start from a checkpoint written after an "
                f"eval pass.")
        return eng

    # -- readout ----------------------------------------------------------

    def set_label_data(self, images: np.ndarray, labels: np.ndarray) -> None:
        """Store the labelled set (encoded once, host-side) that
        :meth:`fit` and every online :meth:`hot_swap` rebuild the vote
        table from (DESIGN.md §15)."""
        imgs = jnp.asarray(np.asarray(images, np.float32))
        xs = [np.asarray(self._encode(imgs[off:off + self.n_slots]))
              for off in range(0, imgs.shape[0], self.n_slots)]
        self._label_set = (np.concatenate(xs, axis=0),
                           np.asarray(labels))

    def fit(self, images: np.ndarray, labels: np.ndarray) -> None:
        """Build the vote-table readout from one labelled pass (the paper's
        neuron-labelling phase; weights are NOT updated — learning stays in
        the training drivers and the §15 online mode). The labelled set is
        kept for online hot swaps to re-label against."""
        self.set_label_data(images, labels)
        x, labs = self._label_set
        self.vote_table = refresh_vote_table(
            self._forward, self.params, x, labs, self.cfg, self.n_slots)

    # -- request loop ------------------------------------------------------

    def submit(self, req: ClassifyRequest) -> None:
        req.t_enqueue = time.perf_counter()
        self.queue.append(req)

    @property
    def pending(self) -> int:
        """Requests not yet retired: queued + riding the in-flight
        dispatch (all of its waves)."""
        inflight = (sum(len(w) for w in self._inflight[0])
                    if self._inflight else 0)
        return len(self.queue) + inflight

    def _require_vote(self) -> None:
        if self.vote_table is None:
            raise RuntimeError("call fit(images, labels) or warm-start with "
                               "from_checkpoint before serving")

    def _admit(self) -> List[ClassifyRequest]:
        """FIFO-admit one wave: up to ``n_slots`` queued requests."""
        return (self._admit_waves(1) or [[]])[0]

    @functools.partial(jax.profiler.annotate_function, name=tracing.ADMIT)
    def _admit_waves(self, max_waves: int) -> List[List[ClassifyRequest]]:
        """FIFO-admit up to ``max_waves`` full-or-partial waves of queued
        requests (only the LAST wave of a dispatch may be partial)."""
        waves: List[List[ClassifyRequest]] = []
        while self.queue and len(waves) < max_waves:
            n = min(self.n_slots, len(self.queue))
            waves.append([self.queue.popleft() for _ in range(n)])
        return waves

    @functools.partial(jax.profiler.annotate_function, name=tracing.STAGE)
    def _stage_wave(self, admitted: List[ClassifyRequest]) -> jax.Array:
        """Host-stack + jitted-encode + no-op-pad one wave's images to the
        fixed (n_slots, S, p) spike shape — the same staging (same encode
        shapes, same pad convention) whether the wave dispatches alone or
        inside a superbatch scan."""
        imgs = jnp.asarray(np.stack(
            [np.asarray(r.image, np.float32) for r in admitted]))
        return pad_batch_rows(self._encode(imgs), self.n_slots, self.T)

    @functools.partial(jax.profiler.annotate_function, name=tracing.DISPATCH)
    def _dispatch(self, admitted: List[ClassifyRequest]) -> jax.Array:
        """Stage one wave and launch it asynchronously: host-side image
        stacking, jitted encode, no-op padding to the fixed slot shape,
        forward, classify. Returns the (still in-flight) predictions —
        nothing here blocks on device results. The published
        (params, vote table, version) tuple is snapshotted EXACTLY once,
        so a hot swap landing mid-flight never mixes versions; in online
        mode the same dispatch also advances the shadow state through
        ``make_online_step`` (pad rows are STDP-inert, so partial waves
        learn only their real rows — DESIGN.md §15)."""
        ps, vt, ver = self._published  # one atomic snapshot per dispatch
        if self._t_first is None:
            self._t_first = time.perf_counter()
        x = self._stage_wave(admitted)
        if self._online is not None:
            self.learn_state, z = self._online(ps, self.learn_state, x)
            self._learn_waves += 1
        else:
            z = self._forward(ps, x)
        for req in admitted:
            req.version = ver
        return self._classify(z, vt)

    @functools.partial(jax.profiler.annotate_function, name=tracing.DISPATCH)
    def _dispatch_super(self,
                        waves: List[List[ClassifyRequest]]) -> jax.Array:
        """Stage K admitted waves and launch them as ONE jitted scan
        dispatch (DESIGN.md §13): per-wave encode + pad reuse the single-
        wave staging shapes, the K-wave forward runs on device with the
        inter-wave loop inside the jit, and the classify readout covers all
        K x n_slots rows at once (classify is row-independent, so per-uid
        results are bit-identical to K separate dispatches). Returns the
        (still in-flight) (k, n_slots) predictions. Online mode scans the
        shadow train step alongside (``make_online_superbatch_step``),
        with the whole superbatch classified under ONE published
        snapshot."""
        ps, vt, ver = self._published  # one atomic snapshot per dispatch
        if self._t_first is None:
            self._t_first = time.perf_counter()
        x_k = jnp.stack([self._stage_wave(w) for w in waves])
        if self._online_sb is not None:
            self.learn_state, z_k = self._online_sb(
                ps, self.learn_state, x_k)
            self._learn_waves += len(waves)
        else:
            z_k = self._forward_sb(ps, x_k)  # (k, slots, S, q)
        for w in waves:
            for req in w:
                req.version = ver
        preds = self._classify(z_k.reshape(-1, *z_k.shape[2:]), vt)
        return preds.reshape(len(waves), self.n_slots)

    @functools.partial(jax.profiler.annotate_function, name=tracing.RETIRE)
    def _retire(self, waves: List[List[ClassifyRequest]],
                preds_dev: jax.Array) -> None:
        """Block on the dispatch's classify readout (the pipeline's ONLY
        sync point) and complete its requests with serve timestamps.
        ``preds_dev`` is (k, n_slots); every wave of the dispatch counts in
        the wave totals, and latency stays per-request."""
        preds = np.asarray(preds_dev)
        now = time.perf_counter()
        for w, admitted in enumerate(waves):
            ver = admitted[0].version  # one snapshot per dispatch: uniform
            for slot, req in enumerate(admitted):
                req.result = int(preds[w, slot])
                req.t_done = now
                self.done[req.uid] = req
                lat = 1e3 * (now - req.t_enqueue) if req.t_enqueue else 0.0
                self._lat_ms.append(lat)
                self._lat_by_ver.setdefault(ver, []).append(lat)
            self._slots_filled += len(admitted)
            self._waves_by_ver[ver] = self._waves_by_ver.get(ver, 0) + 1
            self._slots_by_ver[ver] = (self._slots_by_ver.get(ver, 0)
                                       + len(admitted))
            first, _ = self._span_by_ver.get(ver, (now, now))
            self._span_by_ver[ver] = (first, now)
        self.waves_served += len(waves)
        self._t_last = now

    def _drain_inflight(self) -> int:
        if self._inflight is None:
            return 0
        waves, preds = self._inflight
        self._inflight = None
        self._retire(waves, preds)
        return sum(len(w) for w in waves)

    def _maybe_swap(self) -> None:
        """Run the automatic swap cadence: publish the shadow weights once
        ``swap_every`` learning waves have accumulated. Called at the top
        of every tick — BETWEEN polls — so the wave staged next classifies
        under the fresh version while anything already in flight keeps its
        snapshotted v arrays (DESIGN.md §15)."""
        if self.swap_every and self._learn_waves >= self.swap_every:
            self.hot_swap()

    @functools.partial(jax.profiler.annotate_function, name=tracing.HOT_SWAP)
    def hot_swap(self, block: bool = False) -> int:
        """Atomically publish the shadow weights as version v+1.

        The swap protocol (DESIGN.md §15), in order: (1) re-label — build
        the vote table for the SHADOW weights from the stored labelled set
        via the shared ``refresh_vote_table`` pass (bit-identical to the
        table the trainer would checkpoint for these weights); (2)
        checkpoint — when the engine has a ``ckpt_dir``, shadow state +
        new table go through the crash-safe ``Checkpointer`` in the
        trainer's exact layout, so ``from_checkpoint`` / trainer resume
        both pick the swap up (two swaps landing on one wave re-save the
        same step — safe, see ``checkpointer._write``); (3) publish — ONE
        tuple assignment replaces params + vote table + version, so every
        later dispatch snapshot sees all of v+1 or none of it. The shadow
        keeps learning from its own (published-equal) weights; nothing is
        drained, dropped or duplicated. Returns the new version."""
        if not self.online_stdp:
            raise RuntimeError("hot_swap needs online_stdp=True — serve-"
                               "only engines have no shadow weights")
        if self._label_set is None:
            raise RuntimeError(
                "hot_swap rebuilds the vote table at the new weights and "
                "needs a labelled set: call fit(images, labels) or "
                "set_label_data(images, labels) before swapping")
        # copies: the next online dispatch donates the shadow buffers
        new_ps = [jnp.array(w) for w in
                  params_from_tree(self.learn_state["params"], self.cfg)]
        x, labs = self._label_set
        vt = refresh_vote_table(
            self._forward, new_ps, x, labs, self.cfg, self.n_slots)
        wave = int(self.learn_state["wave"])
        if self.ckpt is not None:
            from repro.checkpoint.checkpointer import tnn_config_fingerprint

            self.ckpt.save(
                wave, dict(self.learn_state, vote_table=vt),
                extra={"arch": "tnn-mnist",
                       "config": tnn_config_fingerprint(self.cfg),
                       "wave": wave, "has_vote": True, "eval_wave": wave,
                       "accuracy": None},
                block=block)
        ps, _, ver = self._published
        self._published = (new_ps, vt, ver + 1)  # the atomic publish
        self.swaps += 1
        self._learn_waves = 0
        return ver + 1

    def step(self) -> int:
        """One LOCK-STEP tick: admit up to ``n_slots`` queued requests, run
        ONE jitted gamma wave for the whole slot batch, block, complete the
        admitted requests. Returns how many requests were served. The
        pipelined path (:meth:`poll`) is the production loop; this is the
        reference the parity tests compare it against."""
        self._require_vote()
        self._maybe_swap()
        if not self.queue:
            return 0
        admitted = self._admit()
        self._retire([admitted], self._dispatch(admitted)[None])
        return len(admitted)

    def poll(self) -> int:
        """One PIPELINED tick: stage + dispatch the next wave (skipped
        entirely when the queue is empty), THEN block on the previously
        in-flight dispatch's readout — so dispatch *i+1*'s host staging and
        device queueing overlap dispatch *i*'s compute. When
        ``superbatch_k > 1`` and the backlog is deeper than one wave, the
        dispatch drains up to ``K x n_slots`` requests as ONE on-device
        K-wave scan (DESIGN.md §13). A due hot swap publishes FIRST, so
        this tick's dispatch already classifies under the new version
        while the still-in-flight one retires under its own snapshot.
        Returns requests retired this tick."""
        self._require_vote()
        self._maybe_swap()
        nxt = None
        if self.queue:
            if self.superbatch_k > 1 and len(self.queue) > self.n_slots:
                k = min(self.superbatch_k,
                        -(-len(self.queue) // self.n_slots))
                waves = self._admit_waves(k)
                nxt = (waves, self._dispatch_super(waves))
            else:
                admitted = self._admit()
                nxt = ([admitted], self._dispatch(admitted)[None])
        served = self._drain_inflight()
        self._inflight = nxt
        return served

    def run_until_done(self, max_ticks: int = 10_000, *,
                       pipelined: bool = True) -> Dict[int, ClassifyRequest]:
        """Serve until the queue drains. ``pipelined=False`` runs the
        lock-step reference loop. Hitting ``max_ticks`` with requests
        outstanding raises :class:`ServeTimeout` instead of silently
        returning a partial ``done`` dict. The timeout path never blocks:
        a wave the double-buffered :meth:`poll` staged but has not retired
        is counted in the UNSERVED split (``in_flight`` on the exception)
        rather than drained — the hung dispatch may be exactly why the
        tick budget ran out — and it stays in flight, so a later
        ``poll``/``run_until_done`` still retires it: served + unserved
        covers every submitted uid with nothing lost or double-counted.
        The split counts THIS call only, so a long-lived engine's earlier
        batches never inflate it."""
        ticks = 0
        served = 0
        while self.queue or self._inflight is not None:
            if ticks >= max_ticks:
                in_flight = (sum(len(w) for w in self._inflight[0])
                             if self._inflight else 0)
                raise ServeTimeout(served=served,
                                   unserved=len(self.queue) + in_flight,
                                   max_ticks=max_ticks,
                                   in_flight=in_flight)
            served += self.poll() if pipelined else self.step()
            ticks += 1
        return self.done

    # -- latency accounting ------------------------------------------------

    @staticmethod
    def _mk_stats(lat_ms: List[float], waves: int, wall: float,
                  slots_filled: int, n_slots: int,
                  compiles: Optional[int] = None) -> ServeStats:
        served = len(lat_ms)
        lat = np.asarray(lat_ms, np.float64)
        return ServeStats(
            requests=served,
            waves=waves,
            wall_s=wall,
            waves_per_s=waves / wall if wall > 0 else 0.0,
            images_per_s=served / wall if wall > 0 else 0.0,
            p50_ms=float(np.percentile(lat, 50)) if served else 0.0,
            p95_ms=float(np.percentile(lat, 95)) if served else 0.0,
            occupancy=(slots_filled / (waves * n_slots)) if waves else 0.0,
            compiles=compiles,
        )

    def stats(self) -> ServeStats:
        """Aggregate the serve record so far (DESIGN.md §12)."""
        wall = ((self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else 0.0)
        return self._mk_stats(self._lat_ms, self.waves_served, wall,
                              self._slots_filled, self.n_slots,
                              tracing.compile_count() - self._compiles0)

    def stats_by_version(self) -> Dict[int, ServeStats]:
        """The serve record split by published version (DESIGN.md §15):
        every request retires under the version its dispatch snapshot
        carried, so each version's requests/waves/latency/occupancy are
        cleanly separable — the per-version accounting the loadgen A/B
        probe reads. Per-version ``wall_s`` spans that version's first to
        last retire."""
        out: Dict[int, ServeStats] = {}
        for ver, lat in sorted(self._lat_by_ver.items()):
            first, last = self._span_by_ver[ver]
            out[ver] = self._mk_stats(
                lat, self._waves_by_ver.get(ver, 0), last - first,
                self._slots_by_ver.get(ver, 0), self.n_slots)
        return out

    def reset(self) -> None:
        """Forget served requests and latency samples between load runs —
        params, vote table, version counter, shadow learning state and
        compiled functions stay warm."""
        self._drain_inflight()
        self.queue.clear()
        self.done = {}
        self.waves_served = 0
        self._lat_ms = []
        self._slots_filled = 0
        self._t_first = self._t_last = None
        self._lat_by_ver = {}
        self._waves_by_ver = {}
        self._slots_by_ver = {}
        self._compiles0 = tracing.compile_count()
        self._span_by_ver = {}
