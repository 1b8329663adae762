"""End to end: client updates folded into the global model over the
window, divided by the window's length (whole rounds)."""


def read(ctx):
    return ctx["completed"] / ctx["window_s"]
