"""Streaming online-STDP trainer for the TNN prototype (DESIGN.md §9).

The LM :class:`repro.train.trainer.Trainer` drives gradient steps; the TNN
prototype learns *online* — STDP updates happen wave-by-wave, exactly as the
silicon applies them — so its trainer drives gamma waves instead:

* **wave batching** — each step is one jitted gamma wave over a fixed-shape
  batch of encoded images through ``core.network.make_train_step`` (forward
  + counter-form STDP, weight buffers donated). With a mesh the batch axis
  is ``shard_map``-sharded over "data" and the site/column axis over
  "model" like ``TNNEngine`` (the spec-driven 2-D factorization of
  DESIGN.md §16); the counters are psum'd, so the learned weights are
  invariant to the whole (data, model) factorization. The network
  config's ``impl`` picks the backend — ``impl="fused"`` collapses the
  whole wave (every layer's forward + STDP counters) into ONE Pallas
  launch (DESIGN.md §10, §11) and trains bit-identically to every other
  backend. The loop is depth-agnostic: the 2-layer prototype and the
  N-layer ``configs.tnn_mnist.deep_config`` cascades train through the
  same step, stream, and checkpoint protocol.
* **K-wave superbatches** — ``superbatch_k > 1`` slices the wave stream
  into K-wave chunks and dispatches each chunk as ONE jitted
  ``core.network.make_superbatch_step`` call: a ``lax.scan`` over K waves
  with the weights donated and the inter-wave state resident on device, so
  the host pays one Python dispatch per K waves instead of per wave
  (DESIGN.md §13). Chunks are clamped at every metrics/eval/checkpoint
  cadence point (boundary semantics), and the per-wave key pre-split makes
  the run — and checkpoint resume — bit-exact for ANY ``superbatch_k``.
* **deterministic stream** — :class:`WaveStream` generates + encodes the
  (reduced) training set once; ``batch_at(wave)`` is a pure function of the
  wave counter, so resume-and-replay is exact (same contract as
  ``data.tokens.TokenStream``).
* **checkpointed resume** — the state pytree (weights, RNG key, wave
  counter) plus the vote table goes through ``checkpoint.Checkpointer``;
  ``maybe_resume`` restores it so train-N, save, restore, train-M produces
  bit-identical weights to training N+M straight through, and
  ``TNNEngine.from_checkpoint`` warm-starts serving without a ``fit`` pass.
* **unsupervised eval cadence** — on ``eval_every`` waves (and at the end)
  a labelled pass over the train set rebuilds the §1 vote-table readout and
  scores held-out accuracy.
* **operator's view** — each dispatch is a ``tnn.train_wave`` profiler step
  holding ``tnn.stage``/``tnn.step``/``tnn.block`` spans, and eval and
  checkpoint run in ``tnn.eval``/``tnn.checkpoint`` (``utils/tracing.py``);
  each JSONL record carries the dispatch's host time ``dt_s`` and
  ``compiles``, the executables obtained since the previous record.

Driver: ``python -m repro.launch.train --arch tnn-mnist [--smoke]``.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import (
    Checkpointer,
    restore_tnn,
    tnn_config_fingerprint,
)
from repro.core.network import (
    NetworkConfig,
    classify,
    encode_images,
    forward_all_padded,
    init_train_state,
    make_superbatch_step,
    make_train_step,
    network_forward,
    params_from_tree,
    refresh_vote_table,
)
from repro.data.mnist_like import digits
from repro.utils import tracing


@dataclasses.dataclass
class TNNTrainConfig:
    """Hyper-parameters for wave-batched online STDP training."""

    epochs: int = 1
    wave_batch: int = 16
    superbatch_k: int = 1          # gamma waves per jitted dispatch (§13)
    train_size: int = 256          # images in the (generated) labelled set
    eval_size: int = 128           # held-out images scored at eval points
    eval_every: int = 0            # waves between evals; 0 = epoch ends only
    ckpt_every: int = 0            # waves between checkpoints; 0 = epoch ends
    ckpt_dir: str = "/tmp/repro_tnn_ckpt"
    keep: int = 3
    seed: int = 0                  # weights + STDP randomness
    data_seed: int = 1             # train-set generator
    eval_seed: int = 2             # held-out-set generator
    log_every: int = 10
    metrics_path: Optional[str] = None

    @property
    def waves_per_epoch(self) -> int:
        return max(self.train_size // self.wave_batch, 1)

    @property
    def total_waves(self) -> int:
        return self.epochs * self.waves_per_epoch


class WaveStream:
    """Deterministic wave-indexed stream of encoded spike batches.

    Generates ``n`` MNIST-like digits once, center-crops them to the
    config's field, and encodes them to (n, sites, p) int8 spike times up
    front; ``batch_at(wave)`` slices ``wave_batch`` rows with wrap-around —
    a pure function of the wave counter, which is what makes checkpoint
    replay exact.
    """

    def __init__(self, cfg: NetworkConfig, n: int, wave_batch: int,
                 seed: int = 1):
        from repro.configs.tnn_mnist import crop_field

        imgs, labels = digits(n, seed=seed)
        imgs = crop_field(imgs, cfg.layers[0].n_cols)
        self.images = imgs
        self.labels = labels
        self.x = np.asarray(encode_images(jnp.asarray(imgs), cfg))
        self.n = n
        self.wave_batch = wave_batch

    def batch_at(self, wave: int) -> np.ndarray:
        idx = (np.arange(self.wave_batch) + wave * self.wave_batch) % self.n
        return self.x[idx]

    def superbatch_at(self, wave: int, k: int) -> np.ndarray:
        """K consecutive wave batches stacked on a leading wave axis
        ((k, wave_batch, C, p)): slice ``i`` IS ``batch_at(wave + i)``, so a
        K-wave superbatch consumes exactly the stream a sequential run
        would (DESIGN.md §13)."""
        return np.stack([self.batch_at(wave + i) for i in range(k)])


class TNNTrainer:
    """Checkpointed, resumable, wave-batched STDP training loop.

    The jitted step donates the state buffers, so only the returned state is
    live; checkpoints materialize to host before the next wave launches.
    Evaluation (vote-table labelling + held-out accuracy) runs unsharded —
    it is a metrics pass, not the hot path.
    """

    def __init__(self, cfg: NetworkConfig, tcfg: TNNTrainConfig, mesh=None):
        cfg.validate()
        if tcfg.superbatch_k < 1:
            raise ValueError(f"superbatch_k={tcfg.superbatch_k} must be >= 1")
        if mesh is not None:
            ndata = int(mesh.shape.get("data", 1))
            if tcfg.wave_batch % max(ndata, 1):
                raise ValueError(
                    f"wave_batch={tcfg.wave_batch} not divisible by data "
                    f"axis size {ndata}")
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.step_fn = make_train_step(cfg, mesh=mesh)
        # one callable serves every chunk size K (compiled per distinct K);
        # built only when superbatching is on, so superbatch_k=1 runs are
        # byte-for-byte the PR-2 lock-step loop.
        self.superbatch_fn = (make_superbatch_step(cfg, mesh=mesh)
                              if tcfg.superbatch_k > 1 else None)
        self.state = init_train_state(jax.random.PRNGKey(tcfg.seed), cfg)
        self.stream = WaveStream(cfg, tcfg.train_size, tcfg.wave_batch,
                                 seed=tcfg.data_seed)
        self.eval_stream = WaveStream(cfg, tcfg.eval_size, tcfg.wave_batch,
                                      seed=tcfg.eval_seed)
        last = cfg.layers[-1]
        self.vote_table = jnp.zeros(
            (last.n_cols, last.column.q, cfg.n_classes), jnp.float32)
        self.has_vote = False
        self._eval_wave = -1  # wave the vote table was last built at
        self.ckpt = Checkpointer(tcfg.ckpt_dir, keep=tcfg.keep)
        self.accuracy: Optional[float] = None
        self._forward = jax.jit(
            lambda ps, x: network_forward(x, ps, self.cfg)[-1])
        self._metrics_f = (open(tcfg.metrics_path, "a")
                           if tcfg.metrics_path else None)

    # -- metrics-handle lifecycle -----------------------------------------

    def close(self) -> None:
        """Flush and close the metrics JSONL handle. Idempotent — ``run``
        calls it from a ``finally`` (so a mid-training exception can't leak
        the handle or drop buffered records), and ``__exit__``/``__del__``
        are the safety nets for trainers that never reach ``run``."""
        f, self._metrics_f = self._metrics_f, None
        if f is not None:
            f.close()

    def __enter__(self) -> "TNNTrainer":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass  # interpreter teardown: never raise from a finalizer

    # -- checkpointing -----------------------------------------------------

    @property
    def wave(self) -> int:
        return int(self.state["wave"])

    def _ckpt_state(self) -> Dict[str, Any]:
        return dict(self.state, vote_table=self.vote_table)

    def checkpoint(self, block: bool = False) -> None:
        with jax.profiler.TraceAnnotation(tracing.CHECKPOINT):
            self.ckpt.save(
                self.wave, self._ckpt_state(),
                extra={"arch": "tnn-mnist",
                       "config": tnn_config_fingerprint(self.cfg),
                       "wave": self.wave, "has_vote": self.has_vote,
                       "eval_wave": self._eval_wave,
                       "accuracy": self.accuracy},
                block=block)

    def maybe_resume(self) -> bool:
        latest = self.ckpt.latest_step()
        if latest is None:
            return False
        state, extra = restore_tnn(self.ckpt, self.cfg, latest)
        self.vote_table = state.pop("vote_table")
        self.state = state
        self.has_vote = bool(extra.get("has_vote", False))
        self._eval_wave = int(extra.get("eval_wave", -1))
        self.accuracy = extra.get("accuracy")
        return True

    # -- readout / eval ----------------------------------------------------

    def _forward_all(self, params, x: np.ndarray) -> jax.Array:
        # ragged tail -> the SAME no-op padding serving uses
        return forward_all_padded(
            self._forward, params, x, self.tcfg.wave_batch,
            self.cfg.layers[0].column.wave.T)

    def evaluate(self) -> float:
        """Labelled pass over the train set -> vote table; score held-out
        accuracy with the soft site vote (the paper's readout, §1). The
        refresh is the shared ``core.network.refresh_vote_table`` path —
        the one the serving engine's online hot swap also runs, so a
        swap-published readout matches the trainer's bit for bit
        (DESIGN.md §15)."""
        with jax.profiler.TraceAnnotation(tracing.EVAL):
            T = self.cfg.layers[-1].column.wave.T
            params = params_from_tree(self.state["params"], self.cfg)
            self.vote_table = refresh_vote_table(
                self._forward, params, self.stream.x, self.stream.labels,
                self.cfg, self.tcfg.wave_batch)
            self.has_vote = True
            z_eval = self._forward_all(params, self.eval_stream.x)
            preds = np.asarray(classify(z_eval, self.vote_table, T,
                                        soft=True))
            self.accuracy = float((preds == self.eval_stream.labels).mean())
            self._eval_wave = self.wave
            return self.accuracy

    # -- the loop ----------------------------------------------------------

    def _log(self, rec: Dict[str, Any]) -> None:
        if self._metrics_f:
            self._metrics_f.write(json.dumps(rec) + "\n")
            self._metrics_f.flush()
        if (self.tcfg.log_every and rec["wave"] % self.tcfg.log_every == 0) \
                or "accuracy" in rec:
            print("[tnn-trainer] " +
                  " ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                           for k, v in rec.items()))

    def run(self) -> Dict[str, Any]:
        # the finally runs on mid-training exceptions too: no leaked handle,
        # no dropped buffered JSONL records
        try:
            return self._run()
        finally:
            self.close()

    def _chunk_k(self, wave: int, total: int) -> int:
        """Waves the next dispatch may run: up to ``superbatch_k``, clamped
        so no eval/checkpoint/epoch cadence point (or the end of training)
        falls MID-superbatch — every cadence action still happens at the
        exact wave count the lock-step loop would perform it at (the §13
        boundary semantics)."""
        tc = self.tcfg
        nxt = total
        if tc.eval_every:
            nxt = min(nxt, (wave // tc.eval_every + 1) * tc.eval_every)
        if tc.ckpt_every:
            nxt = min(nxt, (wave // tc.ckpt_every + 1) * tc.ckpt_every)
        if not tc.eval_every or not tc.ckpt_every:
            wpe = tc.waves_per_epoch
            nxt = min(nxt, (wave // wpe + 1) * wpe)
        return min(tc.superbatch_k, nxt - wave)

    def _run(self) -> Dict[str, Any]:
        resumed = self.maybe_resume()
        if resumed:
            print(f"[tnn-trainer] resumed at wave {self.wave} "
                  f"from {self.tcfg.ckpt_dir}")
        total = self.tcfg.total_waves
        wpe = self.tcfg.waves_per_epoch
        compiles = tracing.compile_count()
        while self.wave < total:
            wave = self.wave
            with jax.profiler.StepTraceAnnotation(tracing.TRAIN_WAVE,
                                                  step_num=wave):
                t0 = time.perf_counter()
                if self.superbatch_fn is None:
                    k = 1
                    with jax.profiler.TraceAnnotation(tracing.STAGE):
                        x = jnp.asarray(self.stream.batch_at(wave))
                    with jax.profiler.TraceAnnotation(tracing.STEP):
                        self.state, z = self.step_fn(self.state, x)
                else:
                    k = self._chunk_k(wave, total)
                    with jax.profiler.TraceAnnotation(tracing.STAGE):
                        x_k = jnp.asarray(self.stream.superbatch_at(wave, k))
                    with jax.profiler.TraceAnnotation(tracing.STEP):
                        self.state, z_k = self.superbatch_fn(self.state, x_k)
                        z = z_k[-1]  # the chunk-end wave's readout
                with jax.profiler.TraceAnnotation(tracing.BLOCK):
                    jax.block_until_ready(z)
                dt = time.perf_counter() - t0
            wave += k
            rec = {"wave": wave, "dt_s": round(dt, 4),
                   "fired": round(float((np.asarray(z) <
                                         self.cfg.layers[-1].column.wave.T)
                                        .mean()), 4)}
            if k > 1:
                rec["superbatch_k"] = k
            at_epoch_end = wave % wpe == 0
            if (self.tcfg.eval_every and wave % self.tcfg.eval_every == 0) or \
                    (not self.tcfg.eval_every and at_epoch_end):
                rec["accuracy"] = self.evaluate()
            now = tracing.compile_count()
            rec["compiles"], compiles = now - compiles, now
            self._log(rec)
            if (self.tcfg.ckpt_every and wave % self.tcfg.ckpt_every == 0) or \
                    (not self.tcfg.ckpt_every and at_epoch_end):
                self.checkpoint()
        # the checkpointed vote table must match the final weights: re-label
        # if any waves ran since the last eval (e.g. eval_every cadence not
        # dividing total_waves), then skip the final save only when the
        # in-loop cadence already wrote this exact state.
        did_final_eval = False
        if self._eval_wave != self.wave:
            self.evaluate()
            did_final_eval = True
        self.ckpt.wait()
        if did_final_eval or self.ckpt.latest_step() != self.wave:
            self.checkpoint(block=True)
            self.ckpt.wait()
        return {
            "final_wave": self.wave,
            "epochs": self.wave // wpe,
            "accuracy": self.accuracy,
            "resumed": resumed,
        }
