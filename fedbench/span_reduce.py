"""The program's own spans in a JAX profiler trace.

``repro.obs.trace.span`` puts each span of the program's work in the
trace as a host event named ``fedhc.<name>``, with its args as the
event's stats.  On top of everything ``trace_reduce`` reads, this module
reads, inside the ``fedbench.window`` annotation:

- ``span_s``: seconds per ``fedhc.*`` name, each event clipped to the
  window (a nested span counts in its own name and in its parent's);
- ``span_n``: events per name;
- ``span_args``: the numeric stats of each name's events, summed;
- ``idle_by_span``: every stretch of the window in which no operation ran
  on the device, split by the innermost ``fedhc.*`` span open on the host
  at the time (``host.other`` where none was), averaged over the devices.

A trace of a program that emits no ``fedhc.*`` span reads empty dicts,
and every metric read from them reads nothing.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import trace_reduce as tr

PREFIX = "fedhc."

Labelled = Tuple[float, float, str]


@dataclass
class SpanSummary(tr.TraceSummary):
    span_s: Dict[str, float] = field(default_factory=dict)
    span_n: Dict[str, int] = field(default_factory=dict)
    span_args: Dict[str, Dict[str, float]] = field(default_factory=dict)
    idle_by_span: Dict[str, float] = field(default_factory=dict)


def innermost(spans: List[Labelled]) -> List[Labelled]:
    """Nested spans (each inside or apart from every other, as on one
    thread) flattened to disjoint segments, each labelled by the innermost
    span open over it; sorted by start."""
    out: List[Labelled] = []
    stack: List[Tuple[float, str]] = []        # (end, name), innermost last
    t = float("-inf")
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, outer = stack.pop()
            out.append((t, end, outer))
            t = max(t, end)
        if stack:
            out.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    while stack:
        end, outer = stack.pop()
        out.append((t, end, outer))
        t = max(t, end)
    return [seg for seg in out if seg[1] > seg[0]]


def _numeric(value) -> bool:
    return isinstance(value, (int, float)) and not isinstance(value, bool)


def reduce_planes(planes) -> Optional[SpanSummary]:
    """``trace_reduce.reduce_planes`` plus the span fields; events may
    carry ``stats`` as (name, value) pairs, as ``ProfileData`` gives them."""
    planes = list(planes)                      # read twice
    base = tr.reduce_planes(planes)
    if base is None:
        return None
    window: Optional[Tuple[float, float]] = None
    spans: List[Tuple[float, float, str, tuple]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = (ev.start_ns + ev.duration_ns) * 1e-9
                if ev.name == tr.WINDOW:
                    window = (s, e)
                elif ev.name.startswith(PREFIX):
                    spans.append((s, e, ev.name, tuple(getattr(ev, "stats", ()))))
    lo, hi = window
    span_s: Dict[str, float] = defaultdict(float)
    span_n: Dict[str, int] = defaultdict(int)
    span_args: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    inside = [sp for sp in spans if sp[1] > lo and sp[0] < hi]
    for s, e, name, stats in inside:
        span_s[name] += min(e, hi) - max(s, lo)
        span_n[name] += 1
        for key, value in stats:
            if _numeric(value):
                span_args[name][key] += value
    segments = [(max(s, lo), min(e, hi), n)
                for s, e, n in innermost([(s, e, n) for s, e, n, _ in inside])]
    segments = [seg for seg in segments if seg[1] > seg[0]]
    idle: Dict[str, float] = defaultdict(float)
    n_dev = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if tr.OPS_LINE not in lines:
            continue
        n_dev += 1
        busy = tr.merge([(max(s, lo), min(e, hi)) for _, s, e in tr._events(lines[tr.OPS_LINE])
                         if e > lo and s < hi])
        for k, v in tr.label_time(tr.gaps(busy, lo, hi), segments).items():
            idle[k] += v
    return SpanSummary(
        **vars(base), span_s=dict(span_s), span_n=dict(span_n),
        span_args={k: dict(v) for k, v in span_args.items()},
        idle_by_span={k: v / n_dev for k, v in idle.items()})


def reduce_file(path) -> Optional[SpanSummary]:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes)


def per_round_ms(ctx, name: str) -> Optional[float]:
    """Milliseconds a window's round spends in span ``name``; None where
    the trace holds no such span."""
    s = getattr(ctx["trace"], "span_s", {}).get(name)
    if s is None or not ctx["rounds"]:
        return None
    return 1e3 * s / ctx["rounds"]
