"""Online STDP on the time-series clustering column: the program's train step
over a stream of seeded series, wave after wave (stage, step, block), as
``TNNTrainer`` steps it, with the program's series encoder on the path at
set-up, as ``TNNTrainer``'s stream encodes its series.

Traffic file parameters:

- ``input``: ``"series"``, the only kind this driver takes.
- ``wave_batch``, ``stream_series``: series per wave (one from each of
  ``wave_batch`` sensor streams), and series in the stream, cycled.
- ``series_len``, ``classes``: the series' shape; refused unless the
  configuration states the same.
- ``check_waves``, ``check_tail_s``: as the ``train`` driver's.

Compared, each against the plain reference (``tnnbench/reference_series``):

- ``x_mismatch``: spike times of the stream, the program's encoder against
  the reference's;
- ``z_mismatch``, ``w_mismatch``: the winner times of the set-up waves and
  every weight after them, the reference following from the seed's
  weights, its own encoding of the series and the seed's key;
- ``z_mismatch.tail``, ``w_mismatch.tail``: the same for the window's last
  waves, the reference following from the weights the program held when
  they began, with its key advanced by the waves run.

A window in which the program compiles or loads an executable fails the
run. The counters carry each kernel's required work a wave
(``tnnbench/work_series``) for the per-kernel roofline readers.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from tnnbench import (common, program, program_mesh, program_series,
                      reference_series, seeds, series, work, work_series)


def run(ctx: common.Ctx) -> common.Run:
    import jax

    cfg, tr = ctx.cfg, ctx.traffic
    net = program_series.network(cfg)
    if tr["input"] != "series":
        raise ValueError(f"input {tr['input']!r}: this driver takes 'series'")
    shape = (int(tr["series_len"]), int(tr["classes"]))
    if shape != (cfg["series_len"], cfg["n_classes"]):
        raise ValueError(f"traffic series (length, classes) {shape} differ "
                         f"from the configuration's")
    B = int(tr["wave_batch"])
    span = common.spans(ctx.trace)
    xs, _ = series.series(int(tr["stream_series"]), *shape,
                          seed=[int(ctx.seed), seeds.TRAIN_IMAGES])
    x = program_series.encode(xs, net)
    ws = reference_series.make_weights(cfg, ctx.seed)
    w0 = [np.asarray(w) for w in ws]
    step = program.train_step(net)
    state = program.train_state(ws, common.stdp_key(ctx.seed))
    del ws

    def call(wave: int):
        """One wave of the window: staged, stepped, blocked."""
        nonlocal state
        with span("bench.stage"):
            xb = jax.numpy.asarray(common.rows(x, B, wave))
        with span("bench.step"):
            state, z = step(state, xb)
        with span("bench.block"):
            jax.block_until_ready(z)
        return z

    # the first waves, through the window's own call and state
    n_check = int(tr["check_waves"])
    head_z = [np.asarray(call(w)) for w in range(n_check)]
    head_w = program.weights(state)

    common.settle()
    if ctx.trace:
        common.start_trace(ctx)
    setup_s = time.perf_counter() - ctx.t_process
    compiles = program_mesh.compile_count()
    tail_at = ctx.seconds - float(tr["check_tail_s"])
    wave, tail_wave, tail_w0, tail_z = n_check, None, None, []
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            if tail_wave is None and time.perf_counter() - t0 >= tail_at:
                tail_wave, tail_w0 = wave, program.weights(state)
            z = call(wave)
            wave += 1
            if tail_wave is not None:
                tail_z.append(z)
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        window_s = time.perf_counter() - t0
    compiles = program_mesh.compile_count() - compiles
    if compiles:
        raise RuntimeError(f"{compiles} executable(s) compiled or loaded "
                           "inside the measured window")
    view = common.stop_trace(ctx) if ctx.trace else None
    peak = common.memory_peak()
    tail_z = [np.asarray(z) for z in tail_z]
    tail_w = program.weights(state)
    waves = wave - n_check
    del state, step, z

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    ref_x = reference_series.encode(xs, cfg)
    key = common.stdp_key(ctx.seed)
    ref_z, ref_w = reference_series.train(
        w0, [common.rows(ref_x, B, w) for w in range(n_check)], key, cfg)
    ref_tz, ref_tw = reference_series.train(
        tail_w0, [common.rows(ref_x, B, w) for w in range(tail_wave, wave)],
        reference_series.advance(key, tail_wave), cfg)
    numbers = {
        "x_mismatch": common.count_diff(x, ref_x),
        "z_mismatch": _diffs(head_z, ref_z),
        "w_mismatch": _diffs(head_w, ref_w),
        "z_mismatch.tail": _diffs(tail_z, ref_tz),
        "w_mismatch.tail": _diffs(tail_w, ref_tw),
    }
    print(f"checked waves 0-{n_check - 1} and {tail_wave}-{wave - 1} in "
          f"{time.perf_counter() - t_ref:.2f} s", file=sys.stderr)
    fwd, stdp = work_series.forward(cfg, B), work_series.stdp(cfg, B)
    per_wave = work.Work(fwd.ops + stdp.ops, fwd.bytes + stdp.bytes)
    return common.Run(
        setup_s=setup_s, window_s=window_s,
        e2e={"train_img_s": waves * B / window_s},
        counters={"waves": waves, "images": waves * B,
                  "tail_waves": wave - tail_wave,
                  **work_series.counters(cfg, B)},
        numbers=numbers, attempted=waves * B, failed=0,
        memory_peak_bytes=peak, kernel_work=per_wave.times(waves),
        step_work=per_wave.times(waves), view=view)


def _diffs(xs, ys) -> int:
    return sum(common.count_diff(a, b) for a, b in zip(xs, ys, strict=True))
