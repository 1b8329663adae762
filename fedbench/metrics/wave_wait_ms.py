"""Local step: host time in the program's ``fedhc.wave.wait`` span
(``fed/batch_exec.py``: the host waits for the wave's program to finish
on the device), per round, in milliseconds, from the profiler trace
(``span_reduce.py``)."""
from span_reduce import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "fedhc.wave.wait")
