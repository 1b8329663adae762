"""Span/event tracer over both the simulated fabric clock and wall clock.

Events are stored as flat tuples (no dataclass, no dict) so a traced
10k-client campaign stays cheap; the export layer (``repro.obs.export``)
converts to Chrome trace-event JSON on demand.

Event tuple layout::

    (ph, name, cat, pid, tid, ts_sim, dur_sim, ts_wall, dur_wall, args)

``ph`` is the Chrome phase ("X" complete span, "i" instant).  ``pid`` and
``tid`` are *names* (tenant / slot / session); the exporter assigns the
numeric ids Perfetto wants.  ``ts_sim`` is fabric-clock seconds (None for
wall-only events); ``ts_wall`` is ``time.time()`` epoch seconds (None for
sim-only events).  ``args`` is a small dict or None.

Hot-path contract: call sites hold a ``self._trace`` reference that is
either a ``Tracer`` or ``None`` and guard with ``if self._trace is not
None`` — with tracing disabled the per-event cost is one attribute load
and a branch, nothing else.  ``NULL_TRACER`` exists for call sites that
prefer unconditional calls; every method is a no-op.

Wall-clock work spans go through :class:`span`, which puts each one in the
JAX profiler trace as ``fedhc.<name>`` (on the clock the device's
operations share) and, given a ``Tracer``, records it here as well.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, str, str, str, str, Optional[float], Optional[float],
              Optional[float], Optional[float], Optional[Any]]

#: High-rate spans may carry ``args`` as a positional tuple instead of a
#: dict (a dict literal is ~40% of the per-event cost on the engine hot
#: path); the exporter zips the tuple with the schema registered here.
ARG_SCHEMAS: Dict[str, Tuple[str, ...]] = {
    "client.exec": ("cid", "round", "budget", "status"),
}


class Tracer:
    """Bounded in-memory trace buffer.

    ``max_events`` caps memory: past the cap, new events are dropped and
    counted in ``drops`` (dropping the *tail* keeps the campaign's start
    intact, which is what you want when a run blows the budget).
    """

    __slots__ = ("enabled", "events", "drops", "max_events", "meta",
                 "_flush_cbs")

    def __init__(self, enabled: bool = True, max_events: int = 1_000_000):
        self.enabled = enabled
        self.events: List[Event] = []
        self.drops = 0
        self.max_events = max_events
        self.meta: Dict[str, Any] = {}
        # deferred-emission hooks: a hot loop may log raw records on the
        # side and register a callback that materializes them into event
        # tuples when the trace is actually read (export/report time) —
        # the campaign engine's client.exec spans work this way
        self._flush_cbs: List[Any] = []

    # -- emission -----------------------------------------------------------

    def span(self, name: str, t0: float, t1: float, pid: str, tid: str,
             cat: str = "sim", args: Optional[Dict[str, Any]] = None) -> None:
        """Complete span on the fabric clock (seconds)."""
        if not self.enabled:
            return
        if len(self.events) >= self.max_events:
            self.drops += 1
            return
        self.events.append(
            ("X", name, cat, pid, tid, t0, t1 - t0, None, None, args))

    def instant(self, name: str, t: float, pid: str, tid: str,
                cat: str = "sim",
                args: Optional[Dict[str, Any]] = None) -> None:
        """Instant event on the fabric clock."""
        if not self.enabled:
            return
        if len(self.events) >= self.max_events:
            self.drops += 1
            return
        self.events.append(
            ("i", name, cat, pid, tid, t, None, None, None, args))

    def wall_span(self, name: str, t0: float, t1: float, pid: str, tid: str,
                  cat: str = "wall",
                  args: Optional[Dict[str, Any]] = None) -> None:
        """Complete span on the wall clock (epoch seconds)."""
        if not self.enabled:
            return
        if len(self.events) >= self.max_events:
            self.drops += 1
            return
        self.events.append(
            ("X", name, cat, pid, tid, None, None, t0, t1 - t0, args))

    def wall_instant(self, name: str, pid: str, tid: str, cat: str = "wall",
                     args: Optional[Dict[str, Any]] = None,
                     t: Optional[float] = None) -> None:
        """Instant event on the wall clock (defaults to now)."""
        if not self.enabled:
            return
        if len(self.events) >= self.max_events:
            self.drops += 1
            return
        self.events.append(("i", name, cat, pid, tid, None, None,
                            time.time() if t is None else t, None, args))

    # -- deferred emission --------------------------------------------------

    def add_flush(self, cb) -> None:
        """Register an idempotent callback that materializes deferred
        records into ``events``; run by :meth:`flush` before any read."""
        self._flush_cbs.append(cb)

    def flush(self) -> None:
        for cb in self._flush_cbs:
            cb()

    # -- access -------------------------------------------------------------

    def __len__(self) -> int:
        self.flush()
        return len(self.events)

    def clear(self) -> None:
        self.events.clear()
        self.drops = 0

    def to_dict(self) -> dict:
        """Raw (pre-export) form: JSON-able, one dict per event (tuple
        args are resolved to dicts via ``ARG_SCHEMAS`` here)."""
        keys = ("ph", "name", "cat", "pid", "tid", "ts_sim", "dur_sim",
                "ts_wall", "dur_wall", "args")
        self.flush()
        events = []
        for ev in self.events:
            d = dict(zip(keys, ev))
            d["args"] = resolve_args(d["name"], d["args"])
            events.append(d)
        return {
            "meta": dict(self.meta),
            "drops": self.drops,
            "events": events,
        }

    def save(self, path: str, clock: str = "sim") -> None:
        """Write a Chrome trace-event JSON file (Perfetto-loadable)."""
        import json

        from .export import to_chrome_trace

        with open(path, "w") as f:
            json.dump(to_chrome_trace(self, clock=clock), f)


def resolve_args(name: str, args) -> Optional[Dict[str, Any]]:
    """Dict form of an event's args: tuples are zipped with the span
    name's ``ARG_SCHEMAS`` entry (positional ``arg0..n`` fallback)."""
    if args is None or isinstance(args, dict):
        return args
    schema = ARG_SCHEMAS.get(name)
    if schema is None or len(schema) != len(args):
        schema = tuple(f"arg{i}" for i in range(len(args)))
    return dict(zip(schema, args))


class NullTracer(Tracer):
    """No-op tracer: safe to call unconditionally, records nothing."""

    __slots__ = ()

    def __init__(self):
        super().__init__(enabled=False, max_events=0)

    def span(self, *a, **kw) -> None:
        pass

    def instant(self, *a, **kw) -> None:
        pass

    def wall_span(self, *a, **kw) -> None:
        pass

    def wall_instant(self, *a, **kw) -> None:
        pass


NULL_TRACER = NullTracer()

#: profiler names are ``PROFILER_PREFIX + name``: the prefix keeps the
#: program's spans apart from a caller's own annotations
PROFILER_PREFIX = "fedhc."


@functools.lru_cache(maxsize=None)
def _annotation():
    """``jax.profiler.TraceAnnotation``, or None without JAX; looked up on
    first use, so importing ``repro.obs`` never imports JAX."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class span:
    """Context manager: one wall-clock span of work.

    Always opens ``jax.profiler.TraceAnnotation("fedhc." + name, **args)``
    (free when no profiler trace is being taken); with a ``tracer`` it also
    records ``name`` as a :meth:`Tracer.wall_span` on ``pid``/``tid`` with
    the same args.  :meth:`set` adds args learnt inside the span;
    ``seconds`` holds the span's duration once it has closed.
    """

    __slots__ = ("name", "tracer", "pid", "tid", "args", "t0", "seconds", "_ann")

    def __init__(self, name: str, tracer: Optional[Tracer] = None, pid: str = "",
                 tid: str = "", **args):
        self.name = name
        self.tracer = tracer
        self.pid = pid
        self.tid = tid
        self.args = args
        self.seconds = 0.0

    def __enter__(self) -> "span":
        ann = _annotation()
        self._ann = None if ann is None else ann(PROFILER_PREFIX + self.name, **self.args)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.time()
        return self

    def set(self, **args) -> None:
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        t1 = time.time()
        self.seconds = t1 - self.t0
        if self.tracer is not None and exc[0] is None:
            self.tracer.wall_span(self.name, self.t0, t1, self.pid, self.tid,
                                  args=self.args or None)
        if self._ann is not None:
            self._ann.__exit__(*exc)
