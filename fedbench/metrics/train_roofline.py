"""Local-step programs (the family's ``STEP_PROGRAMS``; for the image
family the dense wave, which JAX names after its per-client function
`one`, the ragged wave and the per-client step): the least time the
window's client steps need (the larger of their FLOPs over the bf16 peak
and their least bytes over the HBM bandwidth, ``flops.py``) as a
percentage of the device time of those programs in the trace.  The
programs are found by the names JAX gives the jitted functions; where
none is in the trace, nothing is read."""


def read(ctx):
    peak, tr, counts = ctx["peak"], ctx["trace"], ctx["counts"]
    if peak is None or tr is None or not counts.examples:
        return None
    device_s = sum(v for k, v in tr.program_s.items() if k in ctx["step_programs"])
    if device_s <= 0:
        return None
    return 100.0 * counts.least_seconds(peak) / device_s
