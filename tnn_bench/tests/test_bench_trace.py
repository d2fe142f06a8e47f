"""The trace reduction, on a synthetic trace and on a small recorded one."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from tnnbench import readers, trace  # noqa: E402
from tnnbench.trace import Event  # noqa: E402

DEV = "/device:TPU:0"


def synthetic():
    # window 0..1000 ns; ops busy 100-300, 250-400 (overlap), 600-700
    ops = [Event("custom-call.1", 100, 200, "tpu_custom_call"),
           Event("fusion.2", 250, 150), Event("custom-call.1", 600, 100,
                                              "tpu_custom_call"),
           Event("fusion.2", 1500, 100)]          # outside the window
    spans = [Event("bench.window", 0, 1000), Event("bench.stage", 0, 90),
             Event("bench.step", 400, 150), Event("bench.block", 700, 300)]
    return trace.TraceView({DEV: ops}, spans)


def test_busy_idle_and_ops():
    v = synthetic()
    assert v.window == (0, 1000) and v.window_s == pytest.approx(1e-6)
    assert trace.busy_intervals(v.device_ops[DEV], 0, 1000) == [(100, 400), (600, 700)]
    assert trace.busy_s(v) == pytest.approx(400e-9)
    assert trace.top_ops(v) == [["custom-call.1", pytest.approx(300e-9)],
                                ["fusion.2", pytest.approx(150e-9)]]
    assert trace.op_time_s(v, readers.is_wave_kernel) == (pytest.approx(300e-9), 2)
    gaps = dict((k, s) for k, s in trace.idle_gaps(v))
    assert gaps == {"bench.block": pytest.approx(300e-9),
                    "bench.step": pytest.approx(200e-9),
                    "bench.stage": pytest.approx(100e-9)}


def test_readers_on_the_synthetic_trace():
    class Run:
        view = synthetic()
        counters = {"waves": 2}
        peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
        kernel_work = step_work = trace_work = None
        window_s = 1e-6

    from tnnbench.work import Work

    Run.kernel_work = Work(ops=2e3, bytes=100.0)    # least 100 ns in all
    Run.step_work = Run.kernel_work
    assert readers.idle_share(Run) == pytest.approx(60.0)
    assert readers.wave_roofline(Run) == pytest.approx(100 * 100 / 300)
    assert readers.host_us_per_wave(Run) == pytest.approx((90 + 150) * 1e-3 / 2)
    assert readers.step_mfu(Run) == pytest.approx(100 * 2e3 / 1e-6 / 1e12)
    Run.view = None
    assert readers.idle_share(Run) is None and readers.wave_roofline(Run) is None


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    v = trace.load(str(tmp_path))
    assert sum(s.name == "bench.step" for s in v.host_spans) == 3
    lo, hi = v.window
    assert all(lo <= s.start_ns <= hi for s in v.host_spans)
    assert v.device_ops == {}          # the CPU backend has no TPU plane
