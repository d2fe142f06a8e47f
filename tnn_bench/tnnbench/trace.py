"""Reduction from a profiler trace to the numbers the per-layer readers use.

A traced run wraps its measured window in a ``bench.window`` host span and
each of its calls into the program in ``bench.<what>`` spans
(``jax.profiler.TraceAnnotation``). :func:`load` reads the ``.xplane.pb``
the profiler wrote into a :class:`TraceView`: the device operations of every
TPU (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the
``bench.*`` host spans, all on the profiler's one clock. Everything below
works on plain :class:`Event` lists, so it can be checked on a synthetic
trace.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    detail: str = ""   # the op's HLO text and stats, where the trace has them

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceView:
    device_ops: Dict[str, List[Event]]   # device plane name -> its ops
    host_spans: List[Event]              # bench.* spans, the window among them

    @property
    def window(self) -> Tuple[float, float]:
        w = [e for e in self.host_spans if e.name == WINDOW]
        if len(w) != 1:
            raise ValueError(f"trace holds {len(w)} {WINDOW} spans, need 1")
        return w[0].start_ns, w[0].end_ns

    @property
    def window_s(self) -> float:
        a, b = self.window
        return (b - a) * 1e-9


def _detail(ev) -> str:
    stats = dict(ev.stats)
    return " ".join(str(stats[k]) for k in ("long_name", "hlo_category",
                                            "tf_op") if k in stats)


def load(log_dir: str) -> TraceView:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(e.name.split(" = ")[0], e.start_ns,
                                     e.duration_ns, e.name + " " + _detail(e))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return TraceView(device_ops, spans)


# -- reductions ---------------------------------------------------------------


def _clip(events: List[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def busy_intervals(events: List[Event], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """Union of the events' intervals inside [lo, hi], merged and sorted."""
    merged: List[List[float]] = []
    for a, b in sorted(_clip(events, lo, hi)):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_s(view: TraceView) -> float:
    """Seconds in the window in which some operation ran, averaged over the
    traced devices."""
    lo, hi = view.window
    per_dev = [sum(b - a for a, b in busy_intervals(ops, lo, hi)) * 1e-9
               for ops in view.device_ops.values()]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def op_time_s(view: TraceView, match: Callable[[Event], bool]) -> Tuple[float, int]:
    """(seconds, count) of the window's device ops that ``match``, summed
    over devices and averaged per device."""
    lo, hi = view.window
    total, count = 0.0, 0
    for ops in view.device_ops.values():
        for a, b in _clip([e for e in ops if match(e)], lo, hi):
            total += b - a
            count += 1
    n = max(len(view.device_ops), 1)
    return total * 1e-9 / n, count // n


def top_ops(view: TraceView, n: int = 10) -> List[List]:
    """The ``n`` device operations that took most time in the window, as
    [name, seconds] per device on average."""
    lo, hi = view.window
    acc: Dict[str, float] = collections.Counter()
    for ops in view.device_ops.values():
        for e in ops:
            a, b = max(e.start_ns, lo), min(e.end_ns, hi)
            if b > a:
                acc[e.name] += (b - a) * 1e-9
    k = max(len(view.device_ops), 1)
    return [[name, s / k] for name, s in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def _innermost(spans: List[Event], starts: List[float], t: float) -> Optional[str]:
    """The shortest span covering ``t``; ``spans`` sorted by start. Spans
    nest only a few deep, so a short look back from the last start <= t
    finds every candidate."""
    best = None
    i = bisect.bisect_right(starts, t) - 1
    for s in spans[max(i - 8, 0):i + 1]:
        if s.start_ns <= t < s.end_ns and (best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best else None


def idle_gaps(view: TraceView, n: int = 10) -> List[List]:
    """Idle time in the window on the first device, attributed to the bench
    span the host was in at the middle of each gap ("none" where it was in
    none): the ``n`` largest totals as [span, seconds]."""
    lo, hi = view.window
    if not view.device_ops:
        return []
    ops = view.device_ops[sorted(view.device_ops)[0]]
    busy = busy_intervals(ops, lo, hi)
    spans = sorted((s for s in view.host_spans if s.name != WINDOW),
                   key=lambda s: s.start_ns)
    starts = [s.start_ns for s in spans]
    acc: Dict[str, float] = collections.Counter()
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            acc[_innermost(spans, starts, (a + b) / 2) or "none"] += (b - a) * 1e-9
    return [[name, s] for name, s in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
