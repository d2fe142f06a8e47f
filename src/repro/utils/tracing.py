"""The program's tracing: device scopes, host spans, the compile counter and
the jaxpr launch count. Every ``tnn.*`` name is defined here, once.

Device scopes are ``jax.named_scope`` names, placed inside the function that
owns the work so that every step factory inherits them. They land in each
HLO op's ``op_name`` metadata, which a TPU profile shows as the op's
``tf_op``; the benchmark's per-layer readers sum device time by them.
Scopes are compile-time metadata: they add no device work and change no
output bit.

Host spans are ``jax.profiler.TraceAnnotation`` names on the profiler's
clock, so a profile (``--profile DIR`` on either launcher) puts each device
idle gap beside what the host was doing. With no profile running a span
costs one native call on entry and one on exit.

:func:`compile_count` counts every executable the process obtained, compiled
or loaded from the persistent cache: the trainer's JSONL records and
``ServeStats`` report it, so an operator sees which wave recompiled.

:func:`pallas_launch_count` is the metric the fused wave executor moves
(DESIGN.md §10, §11): the per-layer pallas backend issues 2N kernel
launches per learning wave of an N-layer cascade (N forward + N STDP),
``impl="fused"`` issues exactly ONE at any depth. Benchmarks report it
(``benchmarks/run.py``) and the parity tests assert it
(``tests/test_fused_wave.py``, ``tests/test_topology_properties.py``).
"""
from __future__ import annotations

import collections
import threading
from typing import Callable, Dict

import jax

# -- device scopes ------------------------------------------------------------

UNIFORMS = "tnn.uniforms"   # every STDP random draw: the step's key splits,
                            # layer_uniforms, and the up/down split and shard
                            # slice of the draws
WAVE = "tnn.wave"           # a wave kernel with its input preparation (pads,
                            # transposes, casts) and output slicing
APPLY = "tnn.apply"         # the saturating counter apply, core/stdp.apply_net
PSUM = "tnn.psum"           # the counters' all-reduce over "data" (mesh only)
ENCODE = "tnn.encode"       # images to spike times, encode_images
CLASSIFY = "tnn.classify"   # the vote-table readout, classify

# -- host spans ---------------------------------------------------------------

TRAIN_WAVE = "tnn.train_wave"   # one trainer dispatch (a StepTraceAnnotation)
STAGE = "tnn.stage"             # a batch to the device
STEP = "tnn.step"               # the trainer's step or superbatch call
BLOCK = "tnn.block"             # waiting for the dispatch's output
EVAL = "tnn.eval"               # TNNTrainer.evaluate
CHECKPOINT = "tnn.checkpoint"   # TNNTrainer.checkpoint
ADMIT = "tnn.admit"             # the engine takes requests off its queue
DISPATCH = "tnn.dispatch"       # the engine stages and launches a dispatch
RETIRE = "tnn.retire"           # the engine blocks on and completes a dispatch
HOT_SWAP = "tnn.hot_swap"       # re-label, checkpoint, publish the shadow

# -- compiles -----------------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compiles: collections.Counter = collections.Counter()
_lock = threading.Lock()


def _on_duration(event: str, duration_s: float, **kw) -> None:
    if event == COMPILE_EVENT:
        with _lock:
            _compiles[kw.get("fun_name", "?")] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_count() -> int:
    """Executables this process has obtained so far, compiled or loaded from
    the persistent cache. A call that reuses an executable counts nothing."""
    with _lock:
        return sum(_compiles.values())


def compile_counts() -> Dict[str, int]:
    """:func:`compile_count` keyed by the compiled function's name."""
    with _lock:
        return dict(_compiles)


# -- launches -----------------------------------------------------------------


def pallas_launch_count(fn: Callable, *args, **kwargs) -> int:
    """Count ``pallas_call`` equations in ``fn``'s jaxpr (recursing through
    pjit/scan/vmap sub-jaxprs) — the number of kernel launches one call
    issues. vmapped/grid-extended calls count once: they ARE one launch."""

    def walk_param(v) -> int:
        if isinstance(v, (list, tuple)):
            return sum(walk_param(x) for x in v)
        if hasattr(v, "jaxpr"):   # ClosedJaxpr
            return walk(v.jaxpr)
        if hasattr(v, "eqns"):    # Jaxpr
            return walk(v)
        return 0

    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                n += walk_param(v)
        return n

    return walk(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr)
