#!/usr/bin/env python3
"""Records the small trace that ``test_trace_reduce.py`` reads.

    python3 fedbench/tests/record_trace.py <out_dir>

On a TPU: a named jitted program runs three times inside the annotations
the harness uses (``fedbench.window`` around the whole, ``round.collect``
and ``round.aggregate`` around each call), with host sleeps between them,
so the trace holds device work, idle stretches under each label, and one
stretch under none.  Prints the planes and lines it recorded.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def jit_wave_probe(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    jit_wave_probe(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("fedbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("round.collect"):
                jit_wave_probe(x).block_until_ready()
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("round.aggregate"):
                time.sleep(0.003)
            time.sleep(0.001)
    jax.profiler.stop_trace()
    from pathlib import Path

    from jax.profiler import ProfileData

    path = sorted(Path(out_dir).rglob("*.xplane.pb"))[-1]
    print(path, path.stat().st_size)
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = list(line.events)
            print(plane.name, "|", line.name, "|", len(evs), "|",
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:4]])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
