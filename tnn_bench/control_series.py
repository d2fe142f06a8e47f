#!/usr/bin/env python3
"""Readings of the comparison's control and of planted faults for a series
cell, for setting the limits in ``tnn_bench/limits.json``.

    python3 tnn_bench/control_series.py --workload skate-train-series --seeds 1 2 3

For each seed it builds the cell's inputs exactly as a run does (series,
weights and key from the seed, at the cell's own size) and reads the
numbers ``correct`` compares, with the plain reference put in the
program's place:

- ``control``: the reference with its STDP Bernoulli compares in
  bfloat16, where the configuration states float32; it has to fail a
  limit;
- ``encoder_bf16``: the encoder's compares in bfloat16, read by
  ``x_mismatch``, and the waves learned from that encoding;
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

CASES = ("control", "encoder_bf16", "unchanged", "half_batch", "altered")


def series_readings(cfg, tr, seed, tail_waves=40):
    import jax.numpy as jnp
    import numpy as np

    from tnnbench import common, reference_series as ref, seeds, series

    B, n_check = int(tr["wave_batch"]), int(tr["check_waves"])
    T = ref.wave_T(cfg)
    xs, _ = series.series(int(tr["stream_series"]), int(tr["series_len"]),
                          int(tr["classes"]), seed=[int(seed),
                                                    seeds.TRAIN_IMAGES])
    x = ref.encode(xs, cfg)
    x_low = ref.encode(xs, cfg, dtype=jnp.bfloat16)
    w0 = [np.asarray(w) for w in ref.make_weights(cfg, seed)]
    key = common.stdp_key(seed)
    w_head = ref.train(w0, [common.rows(x, B, w) for w in range(n_check)],
                       key, cfg)[1]
    head = (w0, range(n_check), key)
    tail = (w_head, range(n_check, n_check + tail_waves),
            ref.advance(key, n_check))
    out = {case: {} for case in CASES}
    for case in CASES:
        out[case]["x_mismatch"] = 0
    out["encoder_bf16"]["x_mismatch"] = common.count_diff(x_low, x)
    for name, (ws, waves, k) in (("", head), (".tail", tail)):
        rows = [common.rows(x, B, w) for w in waves]
        low_rows = [common.rows(x_low, B, w) for w in waves]
        want = ref.train(ws, rows, k, cfg)

        def numbers(zs, w):
            return {f"z_mismatch{name}": sum(map(common.count_diff, zs, want[0])),
                    f"w_mismatch{name}": sum(map(common.count_diff, w, want[1]))}

        out["control"].update(numbers(*ref.train(ws, rows, k, cfg,
                                                 low=True)))
        out["encoder_bf16"].update(numbers(*ref.train(ws, low_rows, k, cfg)))
        out["unchanged"].update(numbers(
            [ref.forward(v, ws, cfg)[-1] for v in rows], ws))
        half = [v.copy() for v in rows]
        for v in half:
            v[B // 2:] = T
        out["half_batch"].update(numbers(*ref.train(ws, half, k, cfg)))
        altered = [z.copy() for z in want[0]]
        altered[-1][0, 0, 0] = (altered[-1][0, 0, 0] + 1) % (T + 1)
        out["altered"].update(numbers(altered, want[1]))
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
        out = series_readings(cfg, tr, seed, args.tail_waves)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t0, **out}), flush=True)


if __name__ == "__main__":
    main()
