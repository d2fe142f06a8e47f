"""Production training launcher.

On real hardware this builds the production mesh, installs sharding rules,
and runs the fault-tolerant Trainer; on the CPU container it runs the same
code path on the host mesh with a smoke config (--smoke), which is also how
the integration test exercises it.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
        --steps 20 --batch 4 --seq 64

``--arch tnn-mnist`` instead drives the paper's prototype through the
wave-batched online-STDP trainer (DESIGN.md §9): epochs of gamma waves over
the fused Pallas path, vote-table evals, and checkpoints that resume
bit-exactly (re-run the same command to continue a run):

    PYTHONPATH=src python -m repro.launch.train --arch tnn-mnist --smoke \
        --epochs 1 --ckpt-dir /tmp/tnn_ckpt

``--arch tnn-ucr-inlineskate`` trains the time-series clustering column
(one site, 1,882 synapses, 7 neurons; ``configs/tnn_ucr.py``) through the
same trainer, on seeded series of UCR InlineSkate's shape; its vote-table
accuracy is cluster purity:

    PYTHONPATH=src python -m repro.launch.train --arch tnn-ucr-inlineskate \
        --epochs 40 --ckpt-dir /tmp/tnn_ucr_ckpt

``--profile DIR`` records the run with the JAX profiler: the device ops
under their ``tnn.*`` scopes and the trainer's ``tnn.*`` host spans.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.configs.tnn_launch import TNN_ARCHS, launcher_config
from repro.data.tokens import TokenStream
from repro.launch.mesh import (
    describe, make_host_mesh, make_host_mesh_2d, make_production_mesh,
    parse_mesh,
)
from repro.launch.runtime import announce, enable_compile_cache, profiled
from repro.models import model as M
from repro.sharding import partition as PT
from repro.sharding.context import use_partitioning
from repro.train import optimizer as OPT
from repro.train import train_step as TS
from repro.train.trainer import Trainer, TrainerConfig


def train_tnn(args: argparse.Namespace) -> None:
    """Wave-batched online STDP over the prototype or the time-series
    column (DESIGN.md §9)."""
    from repro.train.tnn_trainer import TNNTrainer

    announce("train")
    sites = 16 if args.smoke and args.sites == 625 else args.sites
    cfg, train_config = launcher_config(args.arch, sites, args.depth,
                                        args.impl, args.packed)
    ckpt_dir = args.ckpt_dir or train_config().ckpt_dir
    if args.mesh:
        mesh = make_host_mesh_2d(*parse_mesh(args.mesh))
    else:
        mesh = make_host_mesh()
    tcfg = train_config(
        smoke=args.smoke, epochs=args.epochs,
        ckpt_dir=ckpt_dir, superbatch_k=args.superbatch_k,
        eval_every=args.eval_every, ckpt_every=args.ckpt_every,
        metrics_path=ckpt_dir + "/metrics.jsonl")
    ndata = int(mesh.shape.get("data", 1))
    if tcfg.wave_batch % ndata:
        tcfg = dataclasses.replace(
            tcfg, wave_batch=ndata * max(tcfg.wave_batch // ndata, 1))
    print(f"training {args.arch} ({cfg.n_neurons:,} neurons, "
          f"{cfg.n_synapses:,} synapses, impl={args.impl}) on {describe(mesh)}: "
          f"{tcfg.epochs} epoch(s) x {tcfg.waves_per_epoch} waves "
          f"x batch {tcfg.wave_batch}")
    trainer = TNNTrainer(cfg, tcfg, mesh=mesh)
    print(trainer.run())


def train_lm(args: argparse.Namespace) -> None:
    enable_compile_cache()
    args.ckpt_dir = args.ckpt_dir or "/tmp/repro_launch_ckpt"
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.production_mesh == "multi")
    else:
        mesh = make_host_mesh()
    print(f"training {cfg.name} on {describe(mesh)}")

    prof = PT.RunProfile()
    opt_cfg = OPT.OptConfig(
        name=OPT.default_opt_for(cfg.n_params()), lr=args.lr,
        warmup_steps=min(20, args.steps // 5 + 1), total_steps=args.steps,
        compress_grads=args.compress_grads)
    tc = TS.TrainConfig(micro_steps=args.micro_steps, kv_chunk=128)

    state = TS.init_state(cfg, opt_cfg, jax.random.PRNGKey(0))
    state_sh = PT.shardings_for_tree(
        jax.eval_shape(lambda: state), TS.state_axes(cfg, opt_cfg), mesh,
        PT.param_rules(mesh, prof))
    state = jax.device_put(state, state_sh)

    a_rules = PT.act_rules(mesh, prof)
    raw_step = TS.make_train_step(cfg, opt_cfg, tc)

    def step_fn(st, batch):
        with mesh, use_partitioning(mesh, a_rules):
            return jax.jit(raw_step, in_shardings=(state_sh, None),
                           out_shardings=None)(st, batch)

    stream = TokenStream(cfg.vocab_size, args.batch, args.seq, seed=0)
    tcfg = TrainerConfig(total_steps=args.steps,
                         ckpt_every=max(args.steps // 3, 5),
                         ckpt_dir=args.ckpt_dir, log_every=5,
                         metrics_path=args.ckpt_dir + "/metrics.jsonl")
    trainer = Trainer(step_fn, state, stream, tcfg, shardings=state_sh)
    trainer.install_preemption_handler()
    print(trainer.run())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on the host mesh (CPU container)")
    ap.add_argument("--production-mesh", choices=["single", "multi"], default=None)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--micro-steps", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    # default resolves per arch (LM and TNN runs must not share a dir —
    # resume validates the checkpoint's config fingerprint)
    ap.add_argument("--ckpt-dir", default=None)
    # tnn-mnist options (DESIGN.md §9)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--sites", type=int, default=625,
                    help="prototype sites (perfect square; --smoke -> 16)")
    ap.add_argument("--impl", default="pallas",
                    choices=("direct", "matmul", "pallas", "fused"),
                    help="execution backend; 'fused' = one Pallas launch "
                         "per gamma wave (DESIGN.md §10)")
    ap.add_argument("--depth", type=int, default=2,
                    help="cascade depth: 2 = the paper prototype, other "
                         "depths build the deep_config N-layer cascade "
                         "(DESIGN.md §11; serve with the same --depth)")
    ap.add_argument("--superbatch-k", type=int, default=1,
                    help="gamma waves per jitted dispatch: K > 1 scans K "
                         "waves on device in one launch geometry, clamped "
                         "at eval/checkpoint boundaries — bit-exact with "
                         "K=1 for any K (DESIGN.md §13)")
    ap.add_argument("--packed", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="bit-packed fused-kernel IO: uint8 spike volleys "
                         "/ int8 weights at the pallas_call boundary, "
                         "widening to i32 only inside the kernel; "
                         "--no-packed keeps the legacy i32 layout — "
                         "bit-exact either way (DESIGN.md §14)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="explicit (data, model) host-mesh factorization "
                         "for tnn-mnist, e.g. --mesh 2x2: batch rows shard "
                         "over 'data', TNN sites/columns over 'model' — "
                         "bit-exact under any factorization (DESIGN.md "
                         "§16); default = all local devices on 'data'")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="waves between vote-table evals (0 = epoch ends)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="waves between checkpoints (0 = epoch ends)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record the run with the JAX profiler into DIR: "
                         "device ops under their tnn.* scopes and the "
                         "trainer's tnn.* host spans on one clock")
    args = ap.parse_args()

    with profiled(args.profile):
        if args.arch in TNN_ARCHS:
            train_tnn(args)
        else:
            train_lm(args)


if __name__ == "__main__":
    main()
