"""Jit-ready RG-LRU scan wrapper with impl selection + custom VJP."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.rglru_scan import ref as lru_ref
from repro.kernels.rglru_scan.kernel import rglru_pallas


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rglru_pallas_dif(log_a, b, interpret):
    return rglru_pallas(log_a, b, interpret=interpret)


def _fwd(log_a, b, interpret):
    return _rglru_pallas_dif(log_a, b, interpret), (log_a, b)


def _bwd(interpret, res, cot):
    log_a, b = res
    _, vjp = jax.vjp(lru_ref.rglru_associative, log_a, b)
    return vjp(cot)


_rglru_pallas_dif.defvjp(_fwd, _bwd)


def rglru_scan(
    log_a: jax.Array,
    b: jax.Array,
    impl: str = "associative",
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """h_t = exp(log_a_t)·h_{t-1} + b_t over axis 1.  -> (y, h_final)."""
    if impl == "sequential":
        return lru_ref.rglru_sequential(log_a, b)
    if impl == "associative":
        return lru_ref.rglru_associative(log_a, b)
    if impl == "pallas":
        return _rglru_pallas_dif(log_a, b, resolve_interpret(interpret))
    raise ValueError(f"unknown rglru impl: {impl}")


def rglru_decode_step(
    h: jax.Array, log_a: jax.Array, b: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Single-step update.  h, log_a, b: (B, W).  Returns (y, new_h)."""
    h_new = jnp.exp(log_a.astype(jnp.float32)) * h.astype(jnp.float32) + b.astype(
        jnp.float32
    )
    return h_new.astype(b.dtype), h_new
