"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation of the SSD algorithm [arXiv:2405.21060]: the GPU reference
implementation leans on warp-level parallel prefix sums; on TPU we instead
express each chunk as dense (Q,Q)/(Q,P)/(P,N) matmuls that map directly onto
the MXU, and carry the (P,N) inter-chunk state in a VMEM scratch buffer
across a *sequential* grid dimension (grid = (B, H, L/Q), last axis
"arbitrary" so the carry persists between chunk steps).

All accumulation is float32 regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, s_scr, *, q: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    dt = dt_ref[0, 0, :].astype(jnp.float32).reshape(q, 1)   # (Q,1)
    a = a_ref[0].astype(jnp.float32)
    xq = x_ref[0, 0].astype(jnp.float32)                      # (Q,P)
    bq = b_ref[0, 0].astype(jnp.float32)                      # (Q,N)
    cq = c_ref[0, 0].astype(jnp.float32)                      # (Q,N)

    adt = a * dt                                              # (Q,1)
    cs = jnp.cumsum(adt, axis=0)                              # (Q,1)
    total = cs[q - 1, 0]

    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = rows >= cols
    seg = jnp.exp(jnp.where(tri, cs - cs.reshape(1, q), -1e30))  # (Q,Q)

    scores = jnp.dot(cq, bq.T, preferred_element_type=jnp.float32) * seg
    xdt = xq * dt                                             # (Q,P)
    y = jnp.dot(scores, xdt, preferred_element_type=jnp.float32)

    s_prev = s_scr[...]                                       # (P,N) f32
    y += jnp.exp(cs) * jnp.dot(cq, s_prev.T, preferred_element_type=jnp.float32)

    w = jnp.exp(total - cs) * dt                              # (Q,1)
    local = jnp.dot((xq * w).T, bq, preferred_element_type=jnp.float32)  # (P,N)
    s_new = jnp.exp(total) * s_prev + local
    s_scr[...] = s_new

    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = s_new.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(
    x: jax.Array,   # (B, H, L, P)
    dt: jax.Array,  # (B, H, L)
    a: jax.Array,   # (H,)
    b_mat: jax.Array,  # (B, G, L, N)
    c_mat: jax.Array,  # (B, G, L, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (y (B,H,L,P), final_state (B,H,P,N))."""
    bsz, h, l, p = x.shape
    g, n = b_mat.shape[1], b_mat.shape[3]
    q = min(chunk, l)
    assert l % q == 0, (l, q)
    nc = l // q
    rep = h // g

    grid = (bsz, h, nc)
    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((1, 1, q), lambda b, hh, c: (b, hh, c)),
            pl.BlockSpec((1,), lambda b, hh, c: (hh,)),
            pl.BlockSpec((1, 1, q, n), lambda b, hh, c: (b, hh // rep, c, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b, hh, c: (b, hh // rep, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, a, b_mat, c_mat)
    return y, st
