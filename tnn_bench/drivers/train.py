"""Online STDP training: the program's train step over a stream of seeded
frames, wave after wave (stage, step, block), as ``TNNTrainer`` steps it.

Traffic file parameters:

- ``input``: what the stream holds. ``"frames"``: the seeded frames,
  encoded at set-up by the program's encoder, as ``TNNTrainer``'s stream
  encodes its frames. ``"spikes"``: the same frames as a sensor that
  delivers spike times sends them, encoded by the benchmark in the float32
  the configuration states; the program's encoder is not on this path.
- ``wave_batch``, ``stream_images``: frames per wave, and frames in the
  stream, cycled.
- ``check_waves``: waves run in set-up through the window's own call and
  state, which the window then continues.
- ``check_tail_s``: the window's last waves, from the first that starts
  this many seconds before the close, are compared too.

Compared, each against the plain reference:

- ``x_mismatch`` (``"frames"`` only): spike times of the stream;
- ``z_mismatch``, ``w_mismatch``: the last layer's times of the set-up
  waves and every weight after them, the reference following from the
  seed's weights, frames and key;
- ``z_mismatch.tail``, ``w_mismatch.tail``: the same for the window's
  last waves, the reference following from the weights the program held
  when they began (taken to the host once, at that wave), with its own
  frames and its own key advanced by the waves run.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from tnnbench import common, program, reference, seeds, work


def run(ctx: common.Ctx) -> common.Run:
    import jax

    cfg, tr = ctx.cfg, ctx.traffic
    B = int(tr["wave_batch"])
    span = common.spans(ctx.trace)
    net = program.network(cfg)
    imgs = common.frames(cfg, int(tr["stream_images"]), ctx.seed,
                         seeds.TRAIN_IMAGES)
    ref_x = reference.encode(imgs, cfg)
    if tr["input"] == "frames":
        x = program.encode(imgs, net)
    elif tr["input"] == "spikes":
        x = ref_x
    else:
        raise ValueError(f"input {tr['input']!r}: 'frames' or 'spikes'")
    ws = common.make_weights(cfg, ctx.seed)
    w0 = [np.asarray(w) for w in ws]
    step = program.train_step(net)
    state = program.train_state(ws, common.stdp_key(ctx.seed))

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
    view = common.stop_trace(ctx) if ctx.trace else None
    peak = common.memory_peak()
    tail_z = [np.asarray(z) for z in tail_z]
    tail_w = program.weights(state)
    waves = wave - n_check
    del state, step

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    key = common.stdp_key(ctx.seed)
    ref_z, ref_w = reference.train(
        w0, [common.rows(ref_x, B, w) for w in range(n_check)], key, cfg)
    ref_tz, ref_tw = reference.train(
        tail_w0, [common.rows(ref_x, B, w) for w in range(tail_wave, wave)],
        reference.advance(key, tail_wave), cfg)
    numbers = {}
    if tr["input"] == "frames":
        numbers["x_mismatch"] = common.count_diff(x, ref_x)
    numbers.update({
        "z_mismatch": _diffs(head_z, ref_z),
        "w_mismatch": _diffs(head_w, ref_w),
        "z_mismatch.tail": _diffs(tail_z, ref_tz),
        "w_mismatch.tail": _diffs(tail_w, ref_tw),
    })
    print(f"checked waves 0-{n_check - 1} and {tail_wave}-{wave - 1} in "
          f"{time.perf_counter() - t_ref:.2f} s", file=sys.stderr)
    per_wave = work.wave(cfg, B, learn=True)
    return common.Run(
        setup_s=setup_s, window_s=window_s,
        e2e={"train_img_s": waves * B / window_s},
        counters={"waves": waves, "images": waves * B,
                  "tail_waves": wave - tail_wave},
        numbers=numbers, attempted=waves * B, failed=0,
        memory_peak_bytes=peak, kernel_work=per_wave.times(waves),
        step_work=per_wave.times(waves), view=view)


def _diffs(xs, ys) -> int:
    return sum(common.count_diff(a, b) for a, b in zip(xs, ys, strict=True))
