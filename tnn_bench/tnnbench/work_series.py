"""The work a learning wave of the time-series clustering TNN requires,
split by kernel, and the per-kernel roofline share its readers compute.

A series configuration runs its column as two kernels a wave, the forward
(``tnn_column_forward``) and the STDP counters (``tnn_stdp_update``), so
the yardstick is split the same way. As in
:mod:`tnnbench.work` it counts what the algorithm needs, not what an
implementation does: no padding, no f32 uniforms, no int32 counters.

The configuration is one column of ``S = p * q`` synapses, fan-in ``p``
(the series length) and ``q`` neurons, at one site; each kernel launches
once a wave. Per wave of ``B`` series:

- forward: ``2 * S * T * B`` operations (the RNL compare-and-add each tick);
  bytes: spikes in (``B * p``, uint8), weights read (``S``, int8), winner
  times out (``B * q``, uint8);
- STDP: ``3 * S * B`` operations (the case selection and the two Bernoulli
  compares); bytes: spikes in and winner times read (``B * p + B * q``),
  weights read and written (``2 * S``).
"""
from __future__ import annotations

from typing import Optional, Tuple

from tnnbench import trace
from tnnbench.work import (
    FWD_OPS_PER_SYNAPSE_TICK, STDP_OPS_PER_SYNAPSE_IMAGE, Work)

FORWARD_KERNEL = "tnn_column_forward"
STDP_KERNEL = "tnn_stdp_update"


def column(cfg) -> Tuple[int, int]:
    """(p, q) of the configuration's one column."""
    (q,) = cfg["widths"]
    return cfg["series_len"], q


def forward(cfg, batch: int) -> Work:
    """The column forward kernel's work in one wave of ``batch`` series."""
    p, q = column(cfg)
    S, T = p * q, 1 << cfg["time_bits"]
    return Work(float(FWD_OPS_PER_SYNAPSE_TICK * S * T * batch),
                float(batch * p + S + batch * q))


def stdp(cfg, batch: int) -> Work:
    """The STDP kernel's work in one wave of ``batch`` series."""
    p, q = column(cfg)
    S = p * q
    return Work(float(STDP_OPS_PER_SYNAPSE_IMAGE * S * batch),
                float(batch * (p + q) + 2 * S))


def counters(cfg, batch: int) -> dict:
    """Each kernel's work a wave, as the counters a series driver reports
    for the readers: ``<kernel>.ops`` and ``<kernel>.bytes``."""
    out = {}
    for kernel, w in ((FORWARD_KERNEL, forward(cfg, batch)),
                      (STDP_KERNEL, stdp(cfg, batch))):
        out[f"{kernel}.ops"], out[f"{kernel}.bytes"] = w.ops, w.bytes
    return out


def is_kernel(name: str):
    """Matches the device events of the Mosaic call ``pallas_call`` named
    ``name`` (the HLO custom call carries the kernel's name)."""
    return lambda e: "tpu_custom_call" in e.detail and name in e.name


def kernel_roofline(run, name: str) -> Optional[float]:
    """% of the roofline: the least time the window's required work of
    kernel ``name`` could take on this chip, over the kernel's device time.
    ``None`` where the trace holds no such kernel or the run counted no
    work for it."""
    ops = run.counters.get(f"{name}.ops")
    if run.view is None or run.peaks is None or not ops:
        return None
    kernel_s, n = trace.op_time_s(run.view, is_kernel(name))
    if kernel_s <= 0 or n <= 0:
        return None
    per_wave = Work(ops, run.counters[f"{name}.bytes"])
    return 100.0 * per_wave.least_s(run.peaks) * n / kernel_s
