"""End to end: the 90th percentile of the wall time of every round in the
window (``statistics.quantiles``, exclusive method)."""
import statistics


def read(ctx):
    if len(ctx["round_s"]) < 10:
        return None
    return statistics.quantiles(ctx["round_s"], n=10)[8]
