"""Round control: the harness's host time in the trainer's SAMPLE,
SIMULATE, DISPATCH and REPORT phases (``fed/trainer.py``,
``core/campaign.py``), per round, in milliseconds."""

PHASES = ("sample", "simulate", "dispatch", "report")


def read(ctx):
    if not ctx["rounds"]:
        return None
    return 1e3 * sum(ctx["phase_s"].get(p, 0.0) for p in PHASES) / ctx["rounds"]
