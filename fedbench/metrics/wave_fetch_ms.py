"""Client executor: host time in the program's ``fedhc.wave.fetch`` span
(``fed/batch_exec.py``: the deltas and metrics to the host, split per
client), per round, in milliseconds, from the profiler trace
(``span_reduce.py``)."""
from span_reduce import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "fedhc.wave.fetch")
