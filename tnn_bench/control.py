#!/usr/bin/env python3
"""Readings of the comparison's control and of planted faults, for setting
the limits in ``tnn_bench/limits.json``.

    python3 tnn_bench/control.py --workload proto-train-spikes --seeds 1 2 3

For each seed it builds a training cell's inputs exactly as a run does
(frames, weights and key from the seed, at the cell's own size) and reads
the numbers ``correct`` compares, with the plain reference put in the
program's place:

- ``control``: the reference with its Bernoulli compares in bfloat16, where
  the configuration states float32; it has to fail a limit;
- faults a training cell can have: a step that returns its state
  unchanged, half of the batch left out (its rows carry no spike), and an
  answer altered where it is produced.

The set-up waves start from the seed's weights; the tail stands for the
window's last waves and starts from the weights after them, running
``--tail-waves`` waves (past the stream's wrap). Prints one JSON line per
seed. Needs the chip: the benchmark's runs never call this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def train_readings(cfg, tr, seed, tail_waves=40):
    import numpy as np

    from tnnbench import common, reference, seeds

    B, n_check = int(tr["wave_batch"]), int(tr["check_waves"])
    T = reference.wave_T(cfg)
    imgs = common.frames(cfg, int(tr["stream_images"]), seed, seeds.TRAIN_IMAGES)
    x = reference.encode(imgs, cfg)
    w0 = [np.asarray(w) for w in common.make_weights(cfg, seed)]
    key = common.stdp_key(seed)
    head = (w0, range(n_check), key)
    w_head = reference.train(w0, [common.rows(x, B, w) for w in head[1]], key, cfg)[1]
    tail = (w_head, range(n_check, n_check + tail_waves),
            reference.advance(key, n_check))
    out = {case: {} for case in ("control", "unchanged", "half_batch", "altered")}
    for name, (ws, waves, k) in (("", head), (".tail", tail)):
        xs = [common.rows(x, B, w) for w in waves]
        ref = reference.train(ws, xs, k, cfg)

        def numbers(zs, w):
            return {f"z_mismatch{name}": sum(map(common.count_diff, zs, ref[0])),
                    f"w_mismatch{name}": sum(map(common.count_diff, w, ref[1]))}

        out["control"].update(numbers(*reference.train(ws, xs, k, cfg, low=True)))
        out["unchanged"].update(numbers(
            [reference.forward(v, ws, cfg)[-1] for v in xs], ws))
        half = [v.copy() for v in xs]
        for v in half:
            v[B // 2:] = T
        out["half_batch"].update(numbers(*reference.train(ws, half, k, cfg)))
        altered = [z.copy() for z in ref[0]]
        altered[-1][0, 0, 0] = (altered[-1][0, 0, 0] + 1) % (T + 1)
        out["altered"].update(numbers(altered, ref[1]))
    return out


def main() -> None:
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
    harness.require_chips(int(cell["chips"]))
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = train_readings(cfg, tr, seed, args.tail_waves)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t0, **out}), flush=True)


if __name__ == "__main__":
    main()
