"""Client executor: bytes the waves bring from the device to the host
(the ``d2h_bytes`` arg of the program's ``fedhc.wave.fetch`` spans, from
the ``nbytes`` of the deltas and metrics moved), per round, in MB (1e6
bytes), from the profiler trace (``span_reduce.py``)."""


def read(ctx):
    args = getattr(ctx["trace"], "span_args", {}).get("fedhc.wave.fetch", {})
    if "d2h_bytes" not in args or not ctx["rounds"]:
        return None
    return args["d2h_bytes"] / 1e6 / ctx["rounds"]
