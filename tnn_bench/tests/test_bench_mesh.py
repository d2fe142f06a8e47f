"""The four-chip training cell, run whole with the chip look skipped on four
CPU devices at a small size: sound, `correct` is true; with the timed path
broken underneath, it is false, once for each fault a data-mesh training
cell can have. And the exposed-collective reader on a synthetic trace.

The cell runs in a child process, since the device count is fixed when JAX
starts: ``python3 tnn_bench/tests/test_bench_mesh.py`` prints one JSON line
per case.
"""
import contextlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from tnnbench import harness, trace  # noqa: E402
from tnnbench.trace import Event  # noqa: E402

SEED = 2 ** 33 + 17
CELL = "proto-train-dp4"
FAULTS = ("unchanged_state", "half_batch", "shard_rows_left_out",
          "no_all_reduce", "altered_z")


# -- the child: one run per case ----------------------------------------------


def _case(name, program_mesh):
    """A context under which the cell's timed path has fault ``name``."""
    import jax

    if name == "no_all_reduce":        # each shard applies its own counters
        return _patched(jax.lax, "psum", lambda x, axis_name, **kw: x)
    make, T = program_mesh.train_step, 8

    def wrap(broken):
        def train_step(net, mesh):
            return broken(make(net, mesh), net)
        return _patched(program_mesh, "train_step", train_step)

    if name == "sound":
        return contextlib.nullcontext()
    if name == "unchanged_state":
        def broken(step, net):
            from repro.core.network import network_forward, params_from_tree

            fwd = jax.jit(lambda st, x: network_forward(
                x, params_from_tree(st["params"], net), net)[-1])
            return lambda st, x: (st, fwd(st, x))
        return wrap(broken)
    if name == "half_batch":
        return wrap(lambda step, net: lambda st, x: step(
            st, x.at[x.shape[0] // 2:].set(T)))
    if name == "shard_rows_left_out":  # the last data shard's rows
        return wrap(lambda step, net: lambda st, x: step(
            st, x.at[x.shape[0] * 3 // 4:].set(T)))
    if name == "altered_z":
        def broken(step, net):
            def call(st, x):
                st, z = step(st, x)
                return st, z.at[0, 0, 0].set((z[0, 0, 0] + 1) % (T + 1))
            return call
        return wrap(broken)
    raise KeyError(name)


@contextlib.contextmanager
def _patched(owner, attr, value):
    was = getattr(owner, attr)
    setattr(owner, attr, value)
    try:
        yield
    finally:
        setattr(owner, attr, was)


def main(cases):
    from tnnbench import program

    program.import_program()
    from tnnbench import program_mesh

    m = harness.load_manifest()
    c = harness.cell_entry(m, CELL)
    for name in cases:
        cfg, tr = harness.config_for(m, c), harness.traffic_for(c)
        cfg.update(sites=16, field_side=7)
        cfg["program"]["impl"] = "direct"
        tr.update(stream_images=64)
        with _case(name, program_mesh):
            out = harness.run_cell(CELL, SEED, 0.3, False, time.perf_counter(),
                                   manifest=m, cfg=cfg, traffic=tr,
                                   require_chip=False)
        print(json.dumps({"case": name, "out": out}), flush=True)


# -- the tests ----------------------------------------------------------------


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4").strip())
    done = subprocess.run([sys.executable, __file__, "sound", *FAULTS],
                          env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith("{")]
    return {r["case"]: r["out"] for r in lines}


def test_sound_mesh_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4 and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 64 == 0
    assert set(out["checks"]) == {"z_mismatch", "w_mismatch",
                                  "z_mismatch.tail", "w_mismatch.tail"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_mesh_path_is_not_correct(runs, fault):
    out = runs[fault]
    assert not out["correct"], out["checks"]
    tail = {k: c["value"] for k, c in out["checks"].items()
            if k.endswith(".tail")}
    assert any(tail.values()), out["checks"]


# -- the exposed-collective reader --------------------------------------------

# an op of the four-chip step as the TPU compiler names it, and an op that
# only reads the all-reduce's result
ALL_REDUCE = ("%all-reduce.2 = (s32[625,32,12]{2,1,0:T(8,128)S(1)}, "
              "s32[625,12,10]{2,1,0:T(8,128)S(1)}) all-reduce("
              "%get-tuple-element.10, %get-tuple-element.11), channel_id=1, "
              "replica_groups={{0,1,2,3}}, to_apply=%region_0.3")
READS_IT = ("%get-tuple-element.24 = s32[625,12,10]{2,1,0:T(8,128)S(1)} "
            "get-tuple-element(%all-reduce.2), index=1")


def _reader():
    return harness.reader("collective_exposed_ms.train_dp4")


def _run(device_ops, waves=2):
    class Run:
        view = trace.TraceView(device_ops, [Event("bench.window", 0, 10_000)])
        counters = {"waves": waves}

    return Run()


def test_collectives_are_found_by_opcode():
    is_collective = _reader().__globals__["is_collective"]
    assert is_collective(Event("all-reduce.2", 0, 1, ALL_REDUCE))
    assert not is_collective(Event("get-tuple-element.24", 0, 1, READS_IT))
    for op in ("all-reduce-start", "all-gather-done", "reduce-scatter",
               "collective-permute-start", "all-to-all"):
        assert is_collective(Event(op, 0, 1, f"%x.1 = f32[8] {op}(%y)"))
    assert not is_collective(Event("fusion.3", 0, 1,
                                   "%fusion.3 = f32[8] fusion(%all-gather.1)"))


def test_exposed_collective_time_on_two_devices():
    ar = lambda s, d: Event("all-reduce.2", s, d, ALL_REDUCE)  # noqa: E731
    op = lambda s, d: Event("fusion.1", s, d, "%fusion.1 = f32[8] fusion(%a)")  # noqa: E731
    # device 0: all-reduce 1000-3000, compute over 2500-4000: 1500 exposed;
    # device 1: all-reduce 1000-2000 and 5000-5500, none overlapped: 1500;
    # an all-reduce outside the window counts nothing
    run = _run({"/device:TPU:0": [ar(1000, 2000), op(2500, 1500),
                                  ar(20_000, 500)],
                "/device:TPU:1": [ar(1000, 1000), ar(5000, 500),
                                  op(0, 1000), op(2000, 3000)]})
    assert _reader()(run) == pytest.approx((1500 + 1500) / 2 / 2 * 1e-6)


def test_exposed_collective_time_averages_over_devices():
    ar = Event("all-reduce.2", 0, 4000, ALL_REDUCE)
    run = _run({"/device:TPU:0": [ar], "/device:TPU:1": []}, waves=1)
    assert _reader()(run) == pytest.approx(4000 / 2 * 1e-6)


def test_no_collective_reads_nothing():
    op = Event("fusion.1", 0, 4000, "%fusion.1 = f32[8] fusion(%a)")
    assert _reader()(_run({"/device:TPU:0": [op]})) is None
    assert _reader()(_run({})) is None
    run = _run({"/device:TPU:0": [Event("all-reduce.2", 0, 1000, ALL_REDUCE)]})
    run.view = None
    assert _reader()(run) is None


if __name__ == "__main__":
    main(sys.argv[1:])
