"""Local step on the device: the model FLOPs of the client steps the
traced window completed (``flops.py``, from shapes, forward and backward,
nothing recomputed), as a percentage of what the chip's bf16 peak gives
over the window."""


def read(ctx):
    peak, tr, counts = ctx["peak"], ctx["trace"], ctx["counts"]
    if peak is None or tr is None or not counts.examples:
        return None
    return 100.0 * counts.flops / (tr.window_s * peak["bf16_flops_per_s"] * tr.devices)
