"""Client executor: host time in the program's ``fedhc.wave.prepare`` span
(``fed/batch_exec.py``: pull each client's batches from its shard, pick
the wave's mode, stack the wave's host arrays), per round, in
milliseconds, from the profiler trace (``span_reduce.py``)."""
from span_reduce import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "fedhc.wave.prepare")
