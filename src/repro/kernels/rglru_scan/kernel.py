"""Pallas TPU kernel for the RG-LRU scan.

TPU adaptation: Griffin's GPU kernel relies on warp-synchronous prefix
products; RecurrentGemma's own TPU implementation instead runs the
recurrence *sequentially over time inside the kernel* with the lane (width)
dimension vectorized on the VPU — memory-bound but latency-optimal because
the whole (Q, TW) tile stays resident in VMEM.  We follow that design:
grid = (B, W/TW, L/Q); the hidden state (1, TW) is carried in VMEM scratch
across the sequential chunk axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(la_ref, b_ref, y_ref, hout_ref, h_scr, *, q: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        at = jnp.exp(la_ref[0, t, :].astype(jnp.float32))
        bt = b_ref[0, t, :].astype(jnp.float32)
        h = at * h + bt
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h = lax.fori_loop(0, q, step, h_scr[0, :])
    h_scr[0, :] = h
    hout_ref[0, :] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "tw", "interpret"))
def rglru_pallas(
    log_a: jax.Array,  # (B, L, W)
    b: jax.Array,      # (B, L, W)
    *,
    chunk: int = 256,
    tw: int = 128,
    interpret: bool = False,
):
    """Returns (y (B,L,W), h_final (B,W) float32)."""
    bs, l, w = b.shape
    q = min(chunk, l)
    assert l % q == 0 and w % min(tw, w) == 0, (l, q, w, tw)
    tw = min(tw, w)
    grid = (bs, w // tw, l // q)
    y, hf = pl.pallas_call(
        functools.partial(_rglru_kernel, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, tw), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, q, tw), lambda bi, wi, ci: (bi, ci, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, tw), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, tw), lambda bi, wi, ci: (bi, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs, l, w), b.dtype),
            jax.ShapeDtypeStruct((bs, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, tw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(log_a, b)
    return y, hf
