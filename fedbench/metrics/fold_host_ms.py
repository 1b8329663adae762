"""Aggregation: host time in the program's ``fedhc.fold.sum`` span
(``core/aggregation.py`` ``apply_deltas``: the dispatch of FedAvg's
weighted sum of the round's deltas on the device), per round, in
milliseconds, from the profiler trace (``span_reduce.py``)."""
from span_reduce import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "fedhc.fold.sum")
