"""Pallas TPU split-KV flash-decode kernel with in-kernel int8 dequant.

One query token attends to a long KV cache (the decode_32k/long_500k hot
loop).  The §Perf A4 finding: an int8 cache only halves HBM traffic if the
dequantization happens *inside* the kernel (VMEM/registers) — an XLA-level
dequant materializes the f32 cache in HBM and forfeits the win.  This kernel
streams int8 K/V blocks + per-(position, head) scales from HBM, dequantizes
in VMEM, and runs the online-softmax accumulation — the TPU analogue of
flash-decoding's split-KV loop [arXiv:2311.01282] with KIVI-style
quantization [arXiv:2402.02750].

Layouts: q (B, Hq, D); k/v int8 (B, Hkv, S, D); scales f32 (B, Hkv, S).
``kv_len`` masks the tail (positions ≥ kv_len are dead slots).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr,
    *, tk: int, n_k: int, kv_len: int,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                   # (1, D) pre-scaled
    k_q = k_ref[0, 0].astype(jnp.float32)                 # (TK, D) int8 -> f32
    v_q = v_ref[0, 0].astype(jnp.float32)
    k_s = ks_ref[0, 0].astype(jnp.float32)                # (TK,)
    v_s = vs_ref[0, 0].astype(jnp.float32)
    k = k_q * k_s[:, None]                                # in-VMEM dequant
    v = v_q * v_s[:, None]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (1, TK)
    kpos = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
    s = jnp.where(kpos < kv_len, s, NEG_INF)

    m_prev = m_scr[...]
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
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_len", "tk", "interpret"))
def flash_decode_int8(
    q: jax.Array,        # (B, Hq, D)
    k_q: jax.Array,      # (B, Hkv, S, D) int8
    v_q: jax.Array,      # (B, Hkv, S, D) int8
    k_scale: jax.Array,  # (B, Hkv, S)
    v_scale: jax.Array,  # (B, Hkv, S)
    *,
    kv_len: int,
    tk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns o (B, Hq, D)."""
    b, hq, d = q.shape
    hk, s = k_q.shape[1], k_q.shape[2]
    group = hq // hk
    tk = min(tk, s)
    assert s % tk == 0, (s, tk)
    n_k = s // tk

    scale = 1.0 / math.sqrt(d)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)[:, :, None, :]  # (B,Hq,1,D)

    grid = (b, hq, n_k)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, tk=tk, n_k=n_k, kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, h, ki: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda bi, h, ki: (bi, h // group, ki, 0)),
            pl.BlockSpec((1, 1, tk, d), lambda bi, h, ki: (bi, h // group, ki, 0)),
            pl.BlockSpec((1, 1, tk), lambda bi, h, ki: (bi, h // group, ki)),
            pl.BlockSpec((1, 1, tk), lambda bi, h, ki: (bi, h // group, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda bi, h, ki: (bi, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qs, k_q, v_q, k_scale, v_scale)
    return out[:, :, 0, :]
