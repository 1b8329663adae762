"""Jit-ready grouped-matmul wrapper with impl selection + custom VJP.

impls:
  * "ragged": ``lax.ragged_dot`` — XLA-native, differentiable, the default
    for dry-run lowering and CPU execution.
  * "pallas": the TPU kernel (interpreted only on the CPU backend, see
    ``repro.kernels.resolve_interpret``); backward pass is
    expressed with ``lax.ragged_dot`` transposes via custom_vjp.
  * "dense":  the one-hot oracle (tests/tiny shapes only).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import resolve_interpret
from repro.kernels.grouped_matmul import ref as gmm_ref
from repro.kernels.grouped_matmul.kernel import gmm_pallas


def _pad_to(x: jax.Array, multiple: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas_dif(x, w, group_sizes, interpret):
    m, n = x.shape[0], w.shape[2]
    xp = _pad_to(x, 128, 0)
    wp = _pad_to(w, 128, 2)
    out = gmm_pallas(xp, wp, group_sizes, interpret=interpret)
    return out[:m, :n].astype(x.dtype)


def _gmm_fwd(x, w, group_sizes, interpret):
    return _gmm_pallas_dif(x, w, group_sizes, interpret), (x, w, group_sizes)


def _gmm_bwd(interpret, res, dy):
    x, w, gs = res
    # dx[m] = dy[m] @ w[g(m)]^T  — itself a grouped matmul
    dx = lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), gs).astype(x.dtype)
    # dw[g] = x_g^T @ dy_g — use ragged_dot's own VJP for the weight grad
    _, vjp = jax.vjp(lambda ww: lax.ragged_dot(x, ww, gs), w)
    (dw,) = vjp(dy.astype(x.dtype))
    return dx, dw.astype(w.dtype), None


_gmm_pallas_dif.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(
    x: jax.Array,
    w: jax.Array,
    group_sizes: jax.Array,
    impl: str = "ragged",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """y[m] = x[m] @ w[g(m)] with rows pre-sorted by group."""
    if impl == "ragged":
        return lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))
    if impl == "pallas":
        return _gmm_pallas_dif(x, w, group_sizes.astype(jnp.int32),
                               resolve_interpret(interpret))
    if impl == "dense":
        return gmm_ref.grouped_matmul_ref(x, w, group_sizes).astype(x.dtype)
    raise ValueError(f"unknown grouped_matmul impl: {impl}")
