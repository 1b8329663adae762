"""End to end: process start to the first timed round, compilation or
loading from the compile cache included."""


def read(ctx):
    return ctx["setup_s"]
