"""What every driver under ``tnn_bench/drivers/`` shares: the run's context,
the record a driver returns, the benchmark's spans, trace start and stop,
the end of set-up, and inputs made from the seed.

A driver is a module ``tnn_bench/drivers/<name>.py`` with a function
``run(ctx: Ctx) -> Run``; a traffic file names it under ``"driver"``. It
sets the system up from the seed, warms up the shapes its traffic uses,
drives the program for the window, and checks what the window's entry
produced against the plain reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
from typing import Any, Callable, Dict, Optional

import numpy as np

from tnnbench import reference, seeds, work
from tnnbench.digits import digits


@dataclasses.dataclass
class Ctx:
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_process: float                      # perf_counter at process start
    trace_dir: Optional[str] = None


@dataclasses.dataclass
class Run:
    setup_s: float
    window_s: float                       # host clock, the measured window
    e2e: Dict[str, float]                 # end-to-end values by name
    counters: Dict[str, float]            # what the window did, by name
    numbers: Dict[str, float]             # compared numbers by name
    attempted: int
    failed: int
    memory_peak_bytes: int
    kernel_work: work.Work                # the window's waves, wave kernel only
    step_work: work.Work                  # the window's whole step programs
    view: Any = None                      # trace.TraceView of a traced run
    peaks: Optional[Dict[str, float]] = None   # the chip's, for the readers


def spans(on: bool) -> Callable[[str], Any]:
    """``span("bench.x")`` context: a profiler annotation in a traced run,
    nothing otherwise."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def start_trace(ctx: Ctx) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)


def stop_trace(ctx: Ctx):
    import jax
    from tnnbench import trace

    jax.profiler.stop_trace()
    return trace.load(ctx.trace_dir)


def settle() -> None:
    """End of set-up: collect, then move everything set-up made (the JAX
    runtime's objects, frames) out of the collector's reach, so that a full
    collection inside the window walks only what the window allocates."""
    gc.collect()
    gc.freeze()


def memory_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def make_weights(cfg, seed: int):
    """Per-layer int8 weights, uniform in [0, w_max], made on the device in
    one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    shapes = [(c, p, q) for c, p, q in work.layers(cfg)]
    wm = reference.w_max(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return [jax.random.randint(k, s, 0, wm + 1, dtype=jnp.int8)
                for k, s in zip(keys, shapes)]

    return make(jax.random.PRNGKey(seeds.jax_seed(seed, seeds.WEIGHTS)))


def stdp_key(seed: int):
    """The STDP stream key the program's state starts from."""
    import jax

    return jax.random.PRNGKey(seeds.jax_seed(seed, seeds.STDP_KEY))


def frames(cfg, n: int, seed: int, stream: int) -> np.ndarray:
    """``n`` seeded frames, cropped to the field the site grid sees."""
    imgs, _ = digits(n, seed=[int(seed), stream])
    return np.ascontiguousarray(reference.crop(imgs, cfg))


def count_diff(a, b) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return int(max(a.size, b.size))
    return int(np.count_nonzero(a.astype(np.int64) != b.astype(np.int64)))


def rows(x: np.ndarray, batch: int, wave: int) -> np.ndarray:
    """The rows of stream ``x`` that wave ``wave`` learns from: ``batch``
    consecutive frames, wrapping at the stream's end."""
    return x[(np.arange(batch) + wave * batch) % x.shape[0]]
