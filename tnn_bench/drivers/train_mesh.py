"""Online STDP training on a (data x model) mesh of chips: the program's
sharded train step over a stream of seeded spike volleys, wave after wave
(stage, step, block), each chip receiving its own rows of the wave.

Traffic file parameters:

- ``input``: ``"spikes"``, the seeded frames as a sensor that delivers spike
  times sends them, encoded by the benchmark in the float32 the
  configuration states; the program's encoder is not on this path.
- ``mesh``: ``[data, model]``, the program's host mesh: batch rows shard
  over "data", sites over "model".
- ``wave_batch``, ``stream_images``: frames per wave (all chips together;
  divisible by ``data``), and frames in the stream, cycled.
- ``check_waves``, ``check_tail_s``: as for the one-chip ``train`` driver.

Each wave is staged with ``jax.device_put`` onto the step's batch
sharding, so every chip receives only its own rows. The step sums the
STDP counters over "data" before the one saturating apply, and draws the
uniforms for the whole wave, so its result is the unsharded step's at the
whole batch: the plain reference is compared at ``wave_batch``, exactly.

Compared, each against the plain reference: ``z_mismatch``,
``w_mismatch`` (the set-up waves) and ``z_mismatch.tail``,
``w_mismatch.tail`` (the window's last waves), as the ``train`` driver
compares them. A compile inside the window fails the run.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from tnnbench import common, program, program_mesh, reference, seeds, work


def run(ctx: common.Ctx) -> common.Run:
    import jax

    cfg, tr = ctx.cfg, ctx.traffic
    if tr["input"] != "spikes":
        raise ValueError(f"input {tr['input']!r}: the mesh driver takes "
                         "'spikes'")
    B = int(tr["wave_batch"])
    n_data, n_model = (int(a) for a in tr["mesh"])
    if B % n_data:
        raise ValueError(f"wave_batch {B} does not divide over {n_data} "
                         "data shards")
    span = common.spans(ctx.trace)
    net = program.network(cfg)
    mesh = program_mesh.mesh(n_data, n_model)
    state_sharding, x_sharding = program_mesh.shardings(net, mesh)
    imgs = common.frames(cfg, int(tr["stream_images"]), ctx.seed,
                         seeds.TRAIN_IMAGES)
    x = reference.encode(imgs, cfg)
    ws = common.make_weights(cfg, ctx.seed)
    w0 = [np.asarray(w) for w in ws]
    step = program_mesh.train_step(net, mesh)
    state = jax.device_put(program.train_state(ws, common.stdp_key(ctx.seed)),
                           state_sharding)
    del ws

    def call(wave: int):
        """One wave of the window: staged row shards, stepped, blocked."""
        nonlocal state
        with span("bench.stage"):
            xb = jax.device_put(common.rows(x, B, wave), x_sharding)
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
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in mesh.devices.flat)
    tail_z = [np.asarray(z) for z in tail_z]
    tail_w = program.weights(state)
    waves = wave - n_check
    del state, step, z

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    key = common.stdp_key(ctx.seed)
    ref_z, ref_w = reference.train(
        w0, [common.rows(x, B, w) for w in range(n_check)], key, cfg)
    ref_tz, ref_tw = reference.train(
        tail_w0, [common.rows(x, B, w) for w in range(tail_wave, wave)],
        reference.advance(key, tail_wave), cfg)
    numbers = {
        "z_mismatch": _diffs(head_z, ref_z),
        "w_mismatch": _diffs(head_w, ref_w),
        "z_mismatch.tail": _diffs(tail_z, ref_tz),
        "w_mismatch.tail": _diffs(tail_w, ref_tw),
    }
    print(f"checked waves 0-{n_check - 1} and {tail_wave}-{wave - 1} in "
          f"{time.perf_counter() - t_ref:.2f} s", file=sys.stderr)
    # one chip's share: its rows of the wave over its sites, against one
    # chip's peak in the readers
    share = dict(cfg, sites=cfg["sites"] / n_model)
    per_chip = work.wave(share, B // n_data, learn=True).times(waves)
    return common.Run(
        setup_s=setup_s, window_s=window_s,
        e2e={"train_img_s": waves * B / window_s},
        counters={"waves": waves, "images": waves * B,
                  "tail_waves": wave - tail_wave},
        numbers=numbers, attempted=waves * B, failed=0,
        memory_peak_bytes=peak, kernel_work=per_chip, step_work=per_chip,
        view=view)


def _diffs(xs, ys) -> int:
    return sum(common.count_diff(a, b) for a, b in zip(xs, ys, strict=True))
