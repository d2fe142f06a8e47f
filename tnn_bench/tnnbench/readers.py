"""What the per-layer readers under ``tnn_bench/metrics/`` compute, from a
finished :class:`tnnbench.common.Run`. Each returns ``None`` where it
finds nothing to read; a share of a roofline or a peak is never 0 by
default."""
from __future__ import annotations

from typing import Optional

from tnnbench import trace


def is_wave_kernel(e: trace.Event) -> bool:
    """The fused wave's Mosaic call: the step programs' only custom call."""
    return "tpu_custom_call" in e.detail


def idle_share(run) -> Optional[float]:
    """% of the traced window in which no operation ran on the device."""
    if run.view is None or not run.view.device_ops:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.view) / run.view.window_s)


def wave_roofline(run) -> Optional[float]:
    """% of the roofline: the least time the window's required wave work
    could take on this chip, over the wave kernel's device time."""
    if run.view is None or run.peaks is None or not run.counters.get("waves"):
        return None
    kernel_s, n = trace.op_time_s(run.view, is_wave_kernel)
    if kernel_s <= 0 or n <= 0:
        return None
    least = run.kernel_work.least_s(run.peaks) * n / run.counters["waves"]
    return 100.0 * least / kernel_s


def step_mfu(run) -> Optional[float]:
    """% of the chip's int8 peak: the window's required step operations over
    the window's length."""
    if run.peaks is None or not run.step_work.ops or run.window_s <= 0:
        return None
    return 100.0 * run.step_work.ops / run.window_s / run.peaks["int8_ops_per_s"]


def host_us_per_wave(run) -> Optional[float]:
    """Host microseconds per wave spent staging the batch and calling the
    step, from the benchmark's spans in the traced window."""
    if run.view is None or not run.counters.get("waves"):
        return None
    lo, hi = run.view.window
    s = sum(e.dur_ns for e in run.view.host_spans
            if e.name in ("bench.stage", "bench.step") and lo <= e.start_ns < hi)
    return s * 1e-3 / run.counters["waves"]

