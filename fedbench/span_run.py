#!/usr/bin/env python3
"""One run of ``run.py`` that also reads the program's spans from its trace.

    python3 fedbench/span_run.py --workload <cell> --seed <n> --seconds <s> --trace 1

The same run as ``run.py`` with the same arguments, with its profiler
trace reduced by ``span_reduce`` (which reads everything ``trace_reduce``
reads, and the ``fedhc.*`` spans besides).  It prints ``run.py``'s JSON
line with the span metrics of ``SPAN_METRICS`` (each read by
``metrics/<name>.py``) added under ``metrics``, and ``idle_by_span``,
``span_s``, ``span_n`` and ``span_args`` added under ``run``.  With
``--trace 0`` the line is ``run.py``'s.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import replay  # noqa: E402
import run  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

#: metric name -> unit
SPAN_METRICS = {
    "wave_prepare_ms": "ms",
    "wave_launch_ms": "ms",
    "wave_wait_ms": "ms",
    "wave_fetch_ms": "ms",
    "d2h_mb": "MB",
    "fold_host_ms": "ms",
}


def main(argv: Optional[List[str]] = None, *, emit: Callable[[str], None] = print,
         **run_kw) -> int:
    """``run_kw`` goes to ``run.main`` (``root``, ``require_tpu``)."""
    held = {}

    def reduce_file(path):
        held["summary"] = span_reduce.reduce_file(path)
        return held["summary"]

    # run.main reduces its trace through this module attribute
    lines: List[str] = []
    own, trace_reduce.reduce_file = trace_reduce.reduce_file, reduce_file
    try:
        rc = run.main(argv, emit=lines.append, **{"t_start": T_START, **run_kw})
    finally:
        trace_reduce.reduce_file = own
    if rc or not lines:
        return rc
    result = json.loads(lines[-1])
    summary = held.get("summary")
    if summary is not None:
        ctx = {"trace": summary, "rounds": result["run"]["rounds"]}
        for name, unit in SPAN_METRICS.items():
            mod = replay.load_module(HERE / "metrics" / f"{name}.py", f"fedbench_metric_{name}")
            v = mod.read(ctx)
            if v is not None:
                result["metrics"][name] = {"value": float(v), "unit": unit}
        for key in ("idle_by_span", "span_s", "span_n", "span_args"):
            result["run"][key] = getattr(summary, key)
    emit(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
