#!/usr/bin/env python3
"""Bring-up smoke run of the TNN prototype on a TPU.

    python chip_smoke.py              # one chip: train, checkpoint, serve
    python chip_smoke.py --mesh 2x2   # four chips: the sharded phase only

The default run drives the paper's prototype — 625 sites at depth 2
(13,750 neurons), ``impl="fused"``, packed uint8/int8 kernel IO — through
the code the launchers run (``TNNTrainer`` and ``TNNEngine`` over
``configs.tnn_mnist.launcher_network_config``):

1. a few learning waves at full width, checkpointed into ``--out``;
2. ``TNNEngine.from_checkpoint`` from that checkpoint;
3. a few dozen requests through the pipelined ``run_until_done``.

The same phases run under ``impl="direct"`` on the same seeds, and the
run fails unless trained weights, vote tables and per-uid classifications
match bit for bit, every uid is served exactly once, and the compiled
wave holds a ``tpu_custom_call`` (the Mosaic kernel, not the interpreter).

``--mesh DxM`` runs only the sharded phase: the same training and
pipelined serving on a ``make_host_mesh_2d(D, M)`` mesh, compared bit for
bit with the unsharded single-device run (DESIGN.md §16), plus a check
that the step really splits its arrays across the mesh.

One process holds the chip; nothing here starts a child. The script
exits non-zero when JAX finds no TPU, or when the repository's ``src/``
is not beside it. Its last stdout line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
SITES, DEPTH, BATCH = 625, 2, 16
TRAIN_WAVES, REQUESTS = 4, 48


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[chip-smoke] FAIL: {what}")
    log(f"ok: {what}")


def require_tpu():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"[chip-smoke] FAIL: no TPU found — JAX sees "
            f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs


def train(impl: str, out: Path, mesh=None):
    """``TRAIN_WAVES`` learning waves + the epoch-end eval and checkpoint, as
    ``launch/train.py --arch tnn-mnist`` runs them."""
    from repro.configs.tnn_mnist import launcher_network_config, train_config
    from repro.train.tnn_trainer import TNNTrainer

    cfg = launcher_network_config(SITES, depth=DEPTH, impl=impl)
    tcfg = train_config(sites=SITES, wave_batch=BATCH,
                        train_size=TRAIN_WAVES * BATCH,
                        eval_size=TRAIN_WAVES * BATCH,
                        ckpt_dir=str(out), log_every=1)
    t0 = time.perf_counter()
    trainer = TNNTrainer(cfg, tcfg, mesh=mesh)
    res = trainer.run()
    log(f"train impl={impl} mesh={dict(mesh.shape) if mesh else None}: "
        f"{res['final_wave']} waves, accuracy {res['accuracy']}, "
        f"{time.perf_counter() - t0:.1f} s with compiles")
    return cfg, trainer


def serve(impl: str, cfg, ckpt: Path, mesh=None):
    """Warm-start from ``ckpt`` and serve ``REQUESTS`` requests pipelined, as
    ``launch/serve.py --from-ckpt`` does; returns (engine, uid -> class)."""
    from repro.configs.tnn_mnist import crop_field
    from repro.data.mnist_like import digits
    from repro.serve.tnn_engine import ClassifyRequest, TNNEngine

    t0 = time.perf_counter()
    eng = TNNEngine.from_checkpoint(str(ckpt), cfg, n_slots=BATCH, impl=impl,
                                    mesh=mesh)
    imgs, _ = digits(REQUESTS, seed=2)
    imgs = crop_field(imgs, SITES)
    for uid in range(REQUESTS):
        eng.submit(ClassifyRequest(uid=uid, image=imgs[uid]))
    done = eng.run_until_done(pipelined=True)
    st = eng.stats()
    log(f"serve impl={impl} mesh={dict(mesh.shape) if mesh else None}: "
        f"{st.requests} requests in {st.waves} waves, "
        f"{time.perf_counter() - t0:.1f} s with compiles")
    check(sorted(done) == list(range(REQUESTS)) and st.requests == REQUESTS
          and all(r.result is not None for r in done.values()),
          f"{impl}: every one of {REQUESTS} uids served exactly once")
    return eng, {u: int(r.result) for u, r in done.items()}


def host_tree(trainer):
    import numpy as np

    return {k: np.asarray(v) for k, v in trainer.state["params"].items()}


def same_tree(a, b) -> bool:
    import numpy as np

    return a.keys() == b.keys() and all(np.array_equal(a[k], b[k]) for k in a)


def wave_hlo(trainer) -> str:
    """Compiled text of the trainer's jitted wave step for one batch."""
    import jax.numpy as jnp

    x = jnp.asarray(trainer.stream.batch_at(0))
    return trainer.step_fn.lower(trainer.state, x).compile().as_text()


def single_chip(out: Path) -> None:
    import numpy as np

    cfg, fused = train("fused", out / "fused")
    _, direct = train("direct", out / "direct")
    check(same_tree(host_tree(fused), host_tree(direct)),
          "trained weights: fused == direct, bit for bit")
    check(np.array_equal(np.asarray(fused.vote_table),
                         np.asarray(direct.vote_table)),
          "vote tables: fused == direct")
    check("tpu_custom_call" in wave_hlo(fused),
          "compiled fused wave holds a tpu_custom_call")

    eng, got = serve("fused", cfg, out / "fused")
    check(same_tree({f"layer_{i:02d}": np.asarray(w)
                     for i, w in enumerate(eng.params)}, host_tree(fused)),
          "engine weights == checkpointed trainer weights")
    _, want = serve("direct", cfg, out / "direct")
    check(got == want, f"per-uid classifications: fused == direct "
                       f"({len(got)} uids)")


def meshed(out: Path, data: int, model: int) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.core.network import network_mesh_spec
    from repro.launch.mesh import make_host_mesh_2d

    mesh = make_host_mesh_2d(data, model)
    cfg, ref = train("fused", out / "ref")
    _, sh = train("fused", out / "mesh", mesh=mesh)
    check(same_tree(host_tree(ref), host_tree(sh)),
          f"trained weights: mesh {data}x{model} == unsharded")
    check(np.array_equal(np.asarray(ref.vote_table),
                         np.asarray(sh.vote_table)),
          f"vote tables: mesh {data}x{model} == unsharded")

    # the arrays are really split: the step's readout lands on every mesh
    # device in batch shards, and the compiled Mosaic call runs over the
    # local (sites, batch) slice of each device
    spec = network_mesh_spec(sh.cfg, mesh)
    x = jnp.asarray(sh.stream.batch_at(0))
    hlo = wave_hlo(sh)
    _, z = sh.step_fn(sh.state, x)
    rows = {s.data.shape[0] for s in z.addressable_shards}
    log(f"readout sharding {z.sharding}, shard rows {sorted(rows)}")
    check(len(z.sharding.device_set) == data * model
          and rows == {BATCH // data},
          f"readout split over {data * model} devices, "
          f"{BATCH // data} rows each")
    call = next(l for l in hlo.splitlines() if "tpu_custom_call" in l)
    m = re.search(r"= \((?:u8|s32)\[(\d+),(\d+),\d+\]", call)
    check(m is not None and int(m.group(1)) == spec.local_cols,
          f"kernel grid runs {spec.local_cols} local sites per device "
          f"(got {m.group(0) if m else call[:120]!r})")

    _, want = serve("fused", cfg, out / "ref")
    _, got = serve("fused", cfg, out / "mesh", mesh=mesh)
    check(got == want, f"per-uid classifications: mesh {data}x{model} == "
                       f"unsharded ({len(got)} uids)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", metavar="DxM", default=None,
                    help="run only the sharded phase on a DxM mesh")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="run directory for checkpoints (emptied first)")
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        raise SystemExit(f"[chip-smoke] FAIL: no repository source at {SRC}")
    sys.path.insert(0, str(SRC))
    devs = require_tpu()
    from repro.launch.mesh import parse_mesh
    from repro.launch.runtime import CACHE_DIR, announce

    rep = announce("chip-smoke")
    cache = Path(rep["compile_cache"])
    before = len(list(cache.glob("*"))) if cache.is_dir() else 0
    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    t0 = time.perf_counter()
    if args.mesh:
        meshed(out, *parse_mesh(args.mesh))
    else:
        single_chip(out)
    after = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"done in {time.perf_counter() - t0:.1f} s; compile cache "
        f"{cache}{' (repo default)' if cache == CACHE_DIR else ''}: "
        f"{before} entries before, {after} after")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
