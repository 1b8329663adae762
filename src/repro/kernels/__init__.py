"""Pallas TPU kernels and the one place that decides how they run.

Each kernel lives in its own subpackage as a kernel.py / ops.py / ref.py
triple.  The ops wrappers take ``interpret=None`` and resolve it with
:func:`resolve_interpret`: the Pallas interpreter runs only on the CPU
backend, so on a TPU a ``*_impl="pallas"`` path either compiles or fails
loudly — it never falls back to the interpreter.
"""
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else interpret only on the CPU backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


__all__ = ["resolve_interpret"]
