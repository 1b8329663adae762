"""Aggregation: the harness's host time in the trainer's AGGREGATE phase
(``core/aggregation.py``), with the new global parameters waited for in
the traced run, per round, in milliseconds."""


def read(ctx):
    if not ctx["rounds"]:
        return None
    return 1e3 * ctx["phase_s"].get("aggregate", 0.0) / ctx["rounds"]
