"""repro.obs — the unified observability plane.

One bundle (``ObsPlane``) threads through every layer of the stack:

* ``obs.tracer`` — span/instant events against both the simulated fabric
  clock and the wall clock (``repro.obs.trace``); work spans go through
  ``span``, which also puts them in the JAX profiler trace as
  ``fedhc.<name>``;
* ``obs.registry`` — counters / gauges / bounded histograms with a
  normative name table (``repro.obs.metrics.CANONICAL_METRICS``);
* export — Chrome trace-event / Perfetto JSON (``repro.obs.export``,
  CLI in ``tools/trace_export.py``) and a plain-text report
  (``repro.obs.report``).

Disabled mode is near-zero-cost: ``ObsPlane(trace=False)`` hands out the
shared ``NULL_TRACER`` and call sites cache ``None`` (see the hot-path
contract in ``repro.obs.trace``).
"""
from __future__ import annotations

from typing import Optional

from .metrics import (CANONICAL_METRICS, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .trace import NULL_TRACER, NullTracer, Tracer, span

__all__ = [
    "CANONICAL_METRICS", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_TRACER", "NullTracer", "Tracer", "ObsPlane", "span",
]


class ObsPlane:
    """The observability bundle passed down the stack as ``obs=``."""

    def __init__(self, trace: bool = True, max_events: int = 1_000_000,
                 strict: bool = False):
        self.registry = MetricsRegistry(strict=strict)
        self.tracer: Tracer = Tracer(max_events=max_events) if trace \
            else NULL_TRACER

    @property
    def tracing(self) -> bool:
        return self.tracer.enabled

    def save_trace(self, path: str, clock: str = "sim") -> None:
        self.tracer.save(path, clock=clock)

    def report(self, title: str = "repro.obs report") -> str:
        from .report import render_report

        return render_report(self.registry, tracer=self.tracer, title=title)
