"""From a JAX profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The trace's clock is shared by the host's annotations and the device's
operations.  The harness opens one annotation, ``fedbench.window``, around
its measured window and one, ``round.<phase>``, around each call into the
trainer's phase machine.  This module reads:

- the window: the span of the ``fedbench.window`` annotation;
- busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of every ``/device:`` plane), inside the
  window, averaged over the devices;
- device time per program: the durations of the ``XLA Modules`` line's
  events, by program name (the numeric suffix JAX adds is dropped);
- device time per operation, for the breakdown;
- idle time by phase: every stretch of the window in which no operation
  ran, split by the ``round.<phase>`` annotation open on the host at the
  time (``host.other`` where none was).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "fedbench.window"
PHASE_PREFIX = "round."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OP_NAME_CHARS = 100
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the devices
    devices: int
    program_s: Dict[str, float] = field(default_factory=dict)
    op_s: Dict[str, float] = field(default_factory=dict)
    idle_by_phase: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations with the most device time, each named by
        the head of its HLO text (name, result type)."""
        return [[k[:OP_NAME_CHARS], v] for k, v in
                sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in
                sorted(self.idle_by_phase.items(), key=lambda kv: -kv[1])[:n]]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_time(stretches: List[Interval], labelled: List[Tuple[float, float, str]],
               other: str = "host.other") -> Dict[str, float]:
    """Seconds of ``stretches`` under each label of ``labelled``
    (disjoint, sorted by start); the rest goes to ``other``."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in stretches:
        covered = 0.0
        while j < len(labelled) and labelled[j][1] <= s:
            j += 1
        k = j
        while k < len(labelled) and labelled[k][0] < e:
            a, b, name = labelled[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        if e - s - covered > 0:
            out[other] += e - s - covered
    return dict(out)


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def reduce_planes(planes) -> Optional[TraceSummary]:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events`` having ``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them.  None when the trace holds
    no window or no device."""
    window: Optional[Interval] = None
    phases: List[Tuple[float, float, str]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            devices.append(plane)
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == WINDOW:
                    window = (s, e)
                elif name.startswith(PHASE_PREFIX):
                    phases.append((s, e, name))
    if window is None or not devices:
        return None
    lo, hi = window
    phases = sorted((max(s, lo), min(e, hi), n) for s, e, n in phases
                    if e > lo and s < hi)
    busy_total, program_s, op_s = 0.0, defaultdict(float), defaultdict(float)
    idle = defaultdict(float)
    n_dev = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        n_dev += 1
        ops = []
        for name, s, e in _events(lines[OPS_LINE]):
            if e > lo and s < hi:
                ops.append((max(s, lo), min(e, hi)))
                op_s[name] += min(e, hi) - max(s, lo)
        busy = merge(ops)
        busy_total += sum(e - s for s, e in busy)
        for k, v in label_time(gaps(busy, lo, hi), phases).items():
            idle[k] += v
        if MODULES_LINE in lines:
            for name, s, e in _events(lines[MODULES_LINE]):
                if e > lo and s < hi:
                    program_s[_SUFFIX.sub("", name)] += min(e, hi) - max(s, lo)
    if n_dev == 0:
        return None
    return TraceSummary(
        window_s=hi - lo, busy_s=busy_total / n_dev, devices=n_dev,
        program_s={k: v / n_dev for k, v in program_s.items()},
        op_s={k: v / n_dev for k, v in op_s.items()},
        idle_by_phase={k: v / n_dev for k, v in idle.items()})


def find_xplane(log_dir) -> Optional[str]:
    from pathlib import Path

    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    return str(found[-1]) if found else None


def reduce_file(path) -> Optional[TraceSummary]:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes)
