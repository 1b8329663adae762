"""Pallas TPU flash-attention forward (causal / sliding-window / GQA).

FlashAttention [2205.14135] reworked for the TPU memory hierarchy: the
online-softmax statistics (m, l) and the (TQ, D) output accumulator live in
VMEM scratch and persist across a *sequential* KV-block grid axis; Q/K/V
tiles stream HBM→VMEM via BlockSpecs sized so each (TQ,D)×(D,TK) product is
MXU-shaped.  Causal and sliding-window masks are evaluated from block
coordinates, and fully-masked KV blocks are skipped before their tiles are
consumed (the TPU analogue of FlashAttention's block-skip on the GPU).

Layouts: q (B, Hq, Sq, D), k/v (B, Hkv, Skv, D); GQA via index-map
``h // group`` (no KV duplication in HBM).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, tq: int, tk: int, causal: bool, window: Optional[int], q_offset: int, n_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip test (trace-time where possible)
    q_lo = qi * tq + q_offset
    q_hi = q_lo + tq - 1
    k_lo = ki * tk
    k_hi = k_lo + tk - 1
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window is not None:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (TQ, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (TK, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (TK, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (TQ, TK)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        mask = jnp.bool_(True)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # (TQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "tq", "tk", "interpret")
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, Sq, D), pre-scaled by 1/sqrt(D) upstream? no: scaled here
    k: jax.Array,  # (B, Hk, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    tq: int = 128,
    tk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, d = q.shape
    hk, skv = k.shape[1], k.shape[2]
    group = hq // hk
    tq = min(tq, sq)
    tk = min(tk, skv)
    assert sq % tq == 0 and skv % tk == 0, (sq, tq, skv, tk)
    n_k = skv // tk
    q_offset = skv - sq  # decode/suffix convention

    scale = 1.0 / math.sqrt(d)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    grid = (b, hq, sq // tq, n_k)
    out = pl.pallas_call(
        functools.partial(
            _fa_kernel, tq=tq, tk=tk, causal=causal, window=window,
            q_offset=q_offset, n_k=n_k,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tq, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda bi, h, qi, ki: (bi, h // group, ki, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda bi, h, qi, ki: (bi, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tq, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qs, k, v)
    return out
