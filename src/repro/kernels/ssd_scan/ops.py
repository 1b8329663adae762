"""Jit-ready SSD wrapper: impl selection + custom VJP for the Pallas path.

The model-facing layout is (B, L, H, P) (time-major like attention); the
Pallas kernel wants (B, H, L, P), so this wrapper transposes at the boundary.
Backward for the Pallas impl recomputes through the pure-jnp chunked
algorithm (same math, differentiable), so training on TPU keeps the fused
forward while autodiff stays exact.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.ssd_scan import ref as ssd_ref
from repro.kernels.ssd_scan.kernel import ssd_pallas


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_pallas_dif(x, dt, a, b_mat, c_mat, chunk, interpret):
    l = x.shape[1]
    q = min(chunk, l)
    pad = (-l) % q
    if pad:  # dt=0 padding keeps the final state exact (see ref.ssd_chunked)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    xt = x.transpose(0, 2, 1, 3)          # (B,H,L,P)
    dtt = dt.transpose(0, 2, 1)           # (B,H,L)
    bt = b_mat.transpose(0, 2, 1, 3)      # (B,G,L,N)
    ct = c_mat.transpose(0, 2, 1, 3)
    y, st = ssd_pallas(xt, dtt, a, bt, ct, chunk=q, interpret=interpret)
    return y.transpose(0, 2, 1, 3)[:, :l], st


def _fwd(x, dt, a, b_mat, c_mat, chunk, interpret):
    out = _ssd_pallas_dif(x, dt, a, b_mat, c_mat, chunk, interpret)
    return out, (x, dt, a, b_mat, c_mat)


def _bwd(chunk, interpret, res, cot):
    x, dt, a, b_mat, c_mat = res
    _, vjp = jax.vjp(
        lambda *args: ssd_ref.ssd_chunked(*args, chunk=chunk), x, dt, a, b_mat, c_mat
    )
    return vjp(cot)


_ssd_pallas_dif.defvjp(_fwd, _bwd)


def ssd(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b_mat: jax.Array,
    c_mat: jax.Array,
    *,
    chunk: int = 128,
    impl: str = "chunked",
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """SSD scan.  x (B,L,H,P), dt (B,L,H), a (H,), B/C (B,L,G,N).

    Returns (y (B,L,H,P), final_state (B,H,P,N)).
    """
    if impl == "sequential":
        return ssd_ref.ssd_sequential(x, dt, a, b_mat, c_mat)
    if impl == "chunked":
        return ssd_ref.ssd_chunked(x, dt, a, b_mat, c_mat, chunk=chunk)
    if impl == "pallas":
        return _ssd_pallas_dif(x, dt, a, b_mat, c_mat, chunk,
                               resolve_interpret(interpret))
    raise ValueError(f"unknown ssd impl: {impl}")


def ssd_decode_step(
    state: jax.Array,  # (B, H, P, N)
    x: jax.Array,      # (B, H, P)
    dt: jax.Array,     # (B, H)
    a: jax.Array,      # (H,)
    b_vec: jax.Array,  # (B, G, N)
    c_vec: jax.Array,  # (B, G, N)
) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD update (decode).  Returns (y (B,H,P), new_state)."""
    h = x.shape[1]
    g = b_vec.shape[1]
    rep = h // g
    bh = jnp.repeat(b_vec, rep, axis=1).astype(jnp.float32)
    ch = jnp.repeat(c_vec, rep, axis=1).astype(jnp.float32)
    decay = jnp.exp(a[None, :] * dt.astype(jnp.float32))  # (B,H)
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    state = state * decay[..., None, None] + xdt[..., :, None] * bh[..., None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, ch)
    return y.astype(x.dtype), state
