"""Client executor: host time in the program's ``fedhc.wave.launch`` span
(``fed/batch_exec.py``: the wave's host arrays to the device and the
compiled call, to its asynchronous return), per round, in milliseconds,
from the profiler trace (``span_reduce.py``)."""
from span_reduce import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "fedhc.wave.launch")
