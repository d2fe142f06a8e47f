#!/usr/bin/env python3
"""Readings of the comparison's control and of planted faults for a training
cell on a data mesh, for setting the limits in ``tnn_bench/limits.json``.

    python3 tnn_bench/control_mesh.py --workload proto-train-dp4 --seeds 1 2 3

Beside the readings ``control.py`` takes at the cell's own size (the
control in bfloat16, a state returned unchanged, half of the batch left
out, an answer altered), it reads the faults that only a data mesh can
have, with the plain reference put in the program's place:

- ``shard_rows_left_out``: one data shard's rows carry no spike;
- ``no_all_reduce``: each shard applies only its own rows' counters, so
  each keeps weights of its own; the last-layer times are every shard's,
  the weights the first shard's.

The reference holds no mesh, so this runs on one chip. Prints one JSON
line per seed. The benchmark's runs never call this.
"""
import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tnnbench import common, reference, seeds  # noqa: E402


def shard_train(ws, xs, key, cfg, n_data):
    """``reference.train`` with the counters summed within each of
    ``n_data`` row shards only: (last-layer times per wave over all
    shards, the first shard's final weights), on the host."""
    s = cfg["stdp"]
    static = dict(n=n_data, T=reference.wave_T(cfg), wm=reference.w_max(cfg),
                  mus=(float(s["mu_capture"]), float(s["mu_backoff"]),
                       float(s["mu_search"])),
                  thetas=tuple(theta for _, _, theta in reference.geometry(cfg)))
    table = reference.stabilize_table(cfg)
    wss = [[jnp.asarray(w) for w in ws] for _ in range(n_data)]
    zs = []
    for x in xs:
        key, sub = jax.random.split(key)
        outs = []
        for i in range(n_data):
            wss[i], z = _shard_wave(wss[i], jnp.asarray(x), sub, i, table,
                                    **static)
            outs.append(np.asarray(z))
        zs.append(np.concatenate(outs))
    return zs, [np.asarray(w) for w in wss[0]]


@partial(jax.jit, static_argnames=("n", "T", "wm", "mus", "thetas"))
def _shard_wave(ws, x, key, i, table, *, n, T, wm, mus, thetas):
    """One wave of shard ``i`` of ``n``: its rows of ``x`` through every
    layer, its rows of the wave's global draws, its own counters."""
    B = x.shape[0]
    b = B // n
    x = jax.lax.dynamic_slice_in_dim(x, i * b, b)
    new = []
    for w, theta, lk in zip(ws, thetas, jax.random.split(key, len(ws))):
        z = reference.layer_forward(x, w, theta, T)
        C, p, q = w.shape
        u = jax.lax.dynamic_slice_in_dim(reference._uniforms(lk, C, B, p, q),
                                         i * b, b, axis=2)
        net = reference._stdp_net(w, x, z, u[:, 0], u[:, 1], table, mus, T,
                                  False)
        new.append(jnp.clip(w.astype(jnp.int32) + net, 0, wm).astype(jnp.int8))
        x = z
    return new, x


def mesh_readings(cfg, tr, seed, tail_waves=40):
    """The two mesh faults' numbers, set-up waves and tail, as
    ``control.train_readings`` reads its cases."""
    B, n_check = int(tr["wave_batch"]), int(tr["check_waves"])
    n_data = int(tr["mesh"][0])
    T = reference.wave_T(cfg)
    imgs = common.frames(cfg, int(tr["stream_images"]), seed, seeds.TRAIN_IMAGES)
    x = reference.encode(imgs, cfg)
    w0 = [np.asarray(w) for w in common.make_weights(cfg, seed)]
    key = common.stdp_key(seed)
    head = (w0, range(n_check), key)
    w_head = reference.train(w0, [common.rows(x, B, w) for w in head[1]],
                             key, cfg)[1]
    tail = (w_head, range(n_check, n_check + tail_waves),
            reference.advance(key, n_check))
    out = {"shard_rows_left_out": {}, "no_all_reduce": {}}
    for name, (ws, waves, k) in (("", head), (".tail", tail)):
        xs = [common.rows(x, B, w) for w in waves]
        ref = reference.train(ws, xs, k, cfg)

        def numbers(zs, w):
            return {f"z_mismatch{name}": sum(map(common.count_diff, zs, ref[0])),
                    f"w_mismatch{name}": sum(map(common.count_diff, w, ref[1]))}

        left_out = [v.copy() for v in xs]
        for v in left_out:
            v[B - B // n_data:] = T
        out["shard_rows_left_out"].update(numbers(
            *reference.train(ws, left_out, k, cfg)))
        out["no_all_reduce"].update(numbers(
            *shard_train(ws, xs, k, cfg, n_data)))
    return out


def main() -> None:
    import control
    from tnnbench import harness, program

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--tail-waves", type=int, default=40)
    args = ap.parse_args()
    m = harness.load_manifest()
    cell = harness.cell_entry(m, args.workload)
    cfg, tr = harness.config_for(m, cell), harness.traffic_for(cell)
    program.import_program()
    program.enable_compile_cache()
    harness.require_chips(1)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = control.train_readings(cfg, tr, seed, args.tail_waves)
        out.update(mesh_readings(cfg, tr, seed, args.tail_waves))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t0, **out}),
              flush=True)


if __name__ == "__main__":
    main()
