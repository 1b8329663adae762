"""Client executor: the harness's host time in the trainer's COLLECT phase
(``fed/batch_exec.py``, ``fed/client.py``; it ends when the deltas are on
the host or on the device), per round, in milliseconds."""


def read(ctx):
    if not ctx["rounds"]:
        return None
    return 1e3 * ctx["phase_s"].get("collect", 0.0) / ctx["rounds"]
