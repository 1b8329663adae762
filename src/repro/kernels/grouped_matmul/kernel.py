"""Pallas TPU grouped-matmul (MoE expert GEMM), MegaBlocks adapted to TPU.

GPU MegaBlocks exploits block-sparse CUDA GEMMs over an SM-scheduled grid.
The TPU-native rethink: a *dense* (M/TM, N/TN, G) grid whose (mi, g) cells
are masked out when the M-tile does not intersect group g's row range —
the MXU always runs aligned (TM, K) × (K, TN) tiles resident in VMEM, and
group boundaries are handled by row masks instead of irregular block
pointers (TPU has no warp-level gather; contiguous VMEM tiles + masks keep
the systolic array fed).  The group axis is innermost, so each output
tile stays resident in VMEM while every group accumulates into it: a TPU
pipeline writes an output block back when its index changes and never
reads it again.

Group offsets arrive via scalar prefetch (SMEM) so the index maps can skip
whole tiles before their operands are even fetched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(offs_ref, x_ref, w_ref, out_ref, *, tm: int):
    """One (mi, ni, g) cell: accumulate group g's slice of M-tile mi."""
    mi = pl.program_id(0)
    g = pl.program_id(2)

    row0 = mi * tm
    start = offs_ref[g]
    end = offs_ref[g + 1]

    @pl.when(g == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.logical_and(start < row0 + tm, end > row0))
    def _compute():
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mask = jnp.logical_and(rows >= start, rows < end)
        x = jnp.where(mask, x_ref[...], jnp.zeros_like(x_ref))
        acc = jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)
        out_ref[...] += acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def gmm_pallas(
    x: jax.Array,
    w: jax.Array,
    group_sizes: jax.Array,
    *,
    tm: int = 128,
    tn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K) rows sorted by group; w: (G, K, N); -> (M, N) float32 accum.

    M must be a multiple of tm and N of tn (callers pad).
    """
    m, k = x.shape
    g, _, n = w.shape
    assert m % tm == 0 and n % tn == 0, (m, n, tm, tn)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes).astype(jnp.int32)]
    )
    grid = (m // tm, n // tn, g)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, k), lambda mi, ni, gi, offs: (mi, 0)),
                pl.BlockSpec((1, k, tn), lambda mi, ni, gi, offs: (gi, 0, ni)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda mi, ni, gi, offs: (mi, ni)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(offsets, x, w)
    return out
