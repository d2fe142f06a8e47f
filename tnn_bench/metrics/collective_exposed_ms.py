"""Exposed collective time per wave (ms): the time in which a collective
runs on a device and no other operation runs there, averaged over the
devices and divided by the window's waves.

A collective is found by the HLO opcode in the op's text, since the trace
carries no scopes: ``all-reduce``, ``reduce-scatter``, ``all-gather``,
``collective-permute``, ``all-to-all`` and their ``-start``/``-done``
halves. ``None`` where the window holds no collective.
"""
from __future__ import annotations

import re
from typing import Optional

from tnnbench import trace

COLLECTIVE = re.compile(
    r"\b(?:all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"
    r"(?:-start|-done)?\(")


def is_collective(e: trace.Event) -> bool:
    return bool(COLLECTIVE.search(e.detail))


def _busy_ns(events, lo, hi) -> float:
    return sum(b - a for a, b in trace.busy_intervals(events, lo, hi))


def read(run) -> Optional[float]:
    if run.view is None or not run.counters.get("waves"):
        return None
    lo, hi = run.view.window
    exposed, found = 0.0, False
    for ops in run.view.device_ops.values():
        coll = [e for e in ops if is_collective(e)]
        other = [e for e in ops if not is_collective(e)]
        found = found or bool(trace.busy_intervals(coll, lo, hi))
        # busy with anything, less busy with anything but a collective
        exposed += _busy_ns(ops, lo, hi) - _busy_ns(other, lo, hi)
    if not found:
        return None
    return exposed * 1e-6 / len(run.view.device_ops) / run.counters["waves"]
