"""Device: the peak of device memory in use over the run
(``peak_bytes_in_use``) as a share of the chip's memory in
``peaks.json``."""


def read(ctx):
    peak, used = ctx["peak"], ctx["memory_peak_bytes"]
    if peak is None or not used:
        return None
    return used / peak["hbm_bytes"]
