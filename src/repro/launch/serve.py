"""Production serving launcher — the engines over the host/production mesh.

LM serving (the slot-based continuous-batching engine):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke \
        --requests 6 --slots 2

TNN-as-a-service (the paper's prototype classified over the fused Pallas
path, batch axis data-parallel over the mesh, served through the
continuous-batching wave pipeline of DESIGN.md §12 — ``--lockstep`` falls
back to the blocking reference loop; both print the ``ServeStats`` latency
record):

    PYTHONPATH=src python -m repro.launch.serve --arch tnn-mnist \
        --requests 32 --slots 8 --sites 64 --impl pallas

``--from-ckpt DIR`` warm-starts the engine from a TNN training checkpoint
(weights + vote table, DESIGN.md §9) instead of ad-hoc warm-up + fit —
the deployment path after ``launch/train.py --arch tnn-mnist``:

    PYTHONPATH=src python -m repro.launch.serve --arch tnn-mnist \
        --from-ckpt /tmp/tnn_ckpt --sites 16 --requests 16

``--online-stdp`` turns on learn-while-serving (DESIGN.md §15): every
served wave also runs the STDP epilogue on a shadow state, and every
``--swap-every`` learning waves the engine re-labels, checkpoints and
atomically hot-swaps the published weights/vote table; the run report adds
per-version ServeStats. Combined with ``--from-ckpt`` the shadow stream
CONTINUES the trainer's (restored RNG + wave counter) and swap checkpoints
land back in the same directory:

    PYTHONPATH=src python -m repro.launch.serve --arch tnn-mnist \
        --from-ckpt /tmp/tnn_ckpt --sites 16 --requests 64 \
        --online-stdp --swap-every 4

``--profile DIR`` records the run with the JAX profiler: the device ops
under their ``tnn.*`` scopes and the engine's ``tnn.*`` host spans.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.mesh import (
    describe, make_host_mesh, make_host_mesh_2d, parse_mesh,
)
from repro.launch.runtime import announce, enable_compile_cache, profiled


def serve_lm(args: argparse.Namespace) -> None:
    from repro.models import model as M
    from repro.serve.engine import Engine, Request

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh()
    print(f"serving {cfg.name} on {describe(mesh)}")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, n_slots=args.slots, max_len=args.max_len)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for uid in range(args.requests):
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               int(rng.integers(4, 16))),
                           max_new_tokens=args.max_new))
    done = eng.run_until_done()
    total = sum(len(r.out_tokens) for r in done.values())
    print(f"served {len(done)} requests / {total} tokens "
          f"in {time.time()-t0:.2f}s")


def resolve_slots(requested: int, ndata: int) -> int:
    """Fit the requested slot count to the mesh's data axis by rounding UP
    to the next multiple — never down. (The pre-fix behaviour rounded down,
    silently SHRINKING requested serving capacity: ``--slots 7`` on a
    4-device data axis served 4 slots.) Impossible values error instead of
    being rewritten."""
    if ndata < 1:
        raise ValueError(f"mesh data axis size must be >= 1, got {ndata}")
    if requested < 1:
        raise ValueError(f"--slots must be >= 1, got {requested}")
    resolved = (requested + ndata - 1) // ndata * ndata
    if resolved != requested:
        print(f"[serve] --slots {requested} is not a multiple of the data "
              f"axis size {ndata}; rounding UP to {resolved} slots")
    return resolved


def serve_tnn(args: argparse.Namespace) -> None:
    from repro.configs.tnn_mnist import crop_field, launcher_network_config
    from repro.core import init_network, network_train_wave, encode_images
    from repro.data.mnist_like import digits
    from repro.serve.tnn_engine import ClassifyRequest, TNNEngine
    import jax.numpy as jnp

    announce("serve")
    if args.mesh:
        mesh = make_host_mesh_2d(*parse_mesh(args.mesh))
    else:
        mesh = make_host_mesh()
    # --swap-every has a default so the online quickstart is one flag, but
    # the engine (rightly) refuses a swap cadence with no shadow state —
    # only forward it when online learning is actually on
    swap_every = args.swap_every if args.online_stdp else 0
    n_slots = resolve_slots(args.slots, int(mesh.shape.get("data", 1)))
    cfg = launcher_network_config(args.sites, depth=args.depth,
                                  impl=args.impl, packed=args.packed)
    print(f"serving tnn-mnist ({cfg.n_neurons:,} neurons, impl={args.impl}) "
          f"on {describe(mesh)}")
    lab_imgs, lab_labs = digits(max(128, 4 * n_slots), seed=1)
    lab_imgs = crop_field(lab_imgs, args.sites)
    if args.from_ckpt:
        # trained deployment: weights + vote table from the training
        # checkpoint (rebuilt from label_data when the checkpoint predates
        # any labelling pass), no warm-up needed (DESIGN.md §9); with
        # --online-stdp the shadow stream continues the trainer's and swap
        # checkpoints land back in the same directory (DESIGN.md §15)
        eng = TNNEngine.from_checkpoint(
            args.from_ckpt, cfg, n_slots=n_slots, impl=args.impl, mesh=mesh,
            superbatch_k=args.superbatch_k,
            label_data=(lab_imgs, lab_labs),
            online_stdp=args.online_stdp, swap_every=swap_every)
        print(f"warm-started from {args.from_ckpt} at wave "
              f"{int(eng.learn_state['wave']) if eng.learn_state else '-'}"
              if args.online_stdp else
              f"warm-started from {args.from_ckpt}")
    else:
        params = init_network(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(encode_images(jnp.asarray(lab_imgs), cfg))
        key = jax.random.PRNGKey(1)
        for _ in range(args.train_waves):  # short unsupervised warm-up
            key, k = jax.random.split(key)
            _, params = network_train_wave(x[:16], params, cfg, k)

        eng = TNNEngine(cfg, params, n_slots=n_slots, impl=args.impl,
                        mesh=mesh, superbatch_k=args.superbatch_k,
                        online_stdp=args.online_stdp,
                        swap_every=swap_every)
        eng.fit(lab_imgs, lab_labs)

    test_imgs, test_labs = digits(args.requests, seed=2)
    test_imgs = crop_field(test_imgs, args.sites)
    for uid in range(args.requests):
        eng.submit(ClassifyRequest(uid=uid, image=test_imgs[uid]))
    done = eng.run_until_done(pipelined=not args.lockstep)
    st = eng.stats()
    acc = float(np.mean([done[u].result == test_labs[u] for u in done]))
    mode = "lock-step" if args.lockstep else "pipelined"
    print(f"served {len(done)} images in {st.waves} waves / {st.wall_s:.2f}s "
          f"({mode}), accuracy {acc:.1%}")
    print(f"[serve-stats] {st.waves_per_s:.1f} waves/s  "
          f"{st.images_per_s:.1f} images/s  p50 {st.p50_ms:.1f} ms  "
          f"p95 {st.p95_ms:.1f} ms  occupancy {st.occupancy:.0%}  "
          f"compiles {st.compiles}")
    if args.online_stdp:
        print(f"[online-stdp] learned to wave "
              f"{int(eng.learn_state['wave'])}, {eng.swaps} hot swap(s), "
              f"now serving v{eng.version}")
        for ver, sv in eng.stats_by_version().items():
            v_acc = float(np.mean([done[u].result == test_labs[u]
                                   for u in done
                                   if done[u].version == ver] or [np.nan]))
            print(f"  v{ver}: {sv.requests} requests / {sv.waves} waves  "
                  f"p50 {sv.p50_ms:.1f} ms  p95 {sv.p95_ms:.1f} ms  "
                  f"accuracy {v_acc:.1%}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    # tnn-mnist options
    ap.add_argument("--sites", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2,
                    help="cascade depth: 2 = the paper prototype, other "
                         "depths build the deep_config N-layer cascade "
                         "(DESIGN.md §11; must match the training --depth)")
    ap.add_argument("--impl", default="pallas",
                    choices=("direct", "matmul", "pallas", "fused"),
                    help="execution backend; 'fused' = one Pallas launch "
                         "per gamma wave (DESIGN.md §10)")
    ap.add_argument("--train-waves", type=int, default=4)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="explicit (data, model) host-mesh factorization "
                         "for tnn-mnist, e.g. --mesh 2x2: slots shard over "
                         "'data', TNN sites/columns over 'model' — same "
                         "per-uid results under any factorization "
                         "(DESIGN.md §16); default = all local devices on "
                         "'data'")
    ap.add_argument("--superbatch-k", type=int, default=1,
                    help="max gamma waves one poll dispatch may scan on "
                         "device when the backlog is deeper than --slots: "
                         "K > 1 drains up to K x slots requests per jitted "
                         "dispatch, latency stays per-request "
                         "(DESIGN.md §13)")
    ap.add_argument("--packed", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="bit-packed fused-kernel IO: uint8 spike volleys "
                         "/ int8 weights at the pallas_call boundary; "
                         "--no-packed keeps the legacy i32 layout — "
                         "bit-exact either way, and checkpoints cross the "
                         "flag freely (DESIGN.md §14)")
    ap.add_argument("--lockstep", action="store_true",
                    help="serve with the blocking one-wave-at-a-time loop "
                         "instead of the continuous-batching pipeline "
                         "(the DESIGN.md §12 reference mode)")
    ap.add_argument("--from-ckpt", default=None, metavar="DIR",
                    help="warm-start from a TNN training checkpoint "
                         "(weights + vote table; DESIGN.md §9)")
    ap.add_argument("--online-stdp", action="store_true",
                    help="learn while serving: run the STDP epilogue on "
                         "every served wave into a shadow weight version "
                         "and hot-swap it in on the --swap-every cadence "
                         "(DESIGN.md §15)")
    ap.add_argument("--swap-every", type=int, default=8,
                    help="learning waves between automatic hot swaps in "
                         "--online-stdp mode: each swap re-labels the vote "
                         "table at the shadow weights, checkpoints, and "
                         "publishes atomically; 0 swaps only on explicit "
                         "hot_swap() calls (DESIGN.md §15)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record the run with the JAX profiler into DIR: "
                         "device ops under their tnn.* scopes and the "
                         "engine's tnn.* host spans on one clock")
    args = ap.parse_args()
    with profiled(args.profile):
        if args.arch == "tnn-mnist":
            serve_tnn(args)
        else:
            serve_lm(args)


if __name__ == "__main__":
    main()
