"""Device: the share of the traced window in which no operation ran on
the device (1 - union of the device's operation intervals / window), from
the profiler trace."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return tr.idle_frac
