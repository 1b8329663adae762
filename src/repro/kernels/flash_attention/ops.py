"""Jit-ready flash-attention wrapper (layout adaptation + custom VJP).

Model-facing layout is (B, S, H, D); the kernel wants (B, H, S, D).
Backward recomputes through the pure-JAX chunked online-softmax attention
(identical math) so the fused forward remains trainable.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fa_dif(q, k, v, causal, window, interpret):
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_pallas(
        qt, kt, vt, causal=causal, window=window, interpret=interpret
    )
    return out.transpose(0, 2, 1, 3)


def _ref(q, k, v, causal, window):
    from repro.models.layers import attention_chunked

    b, sq = q.shape[0], q.shape[1]
    skv = k.shape[1]
    qpos = jnp.broadcast_to(jnp.arange(skv - sq, skv), (b, sq))
    kpos = jnp.broadcast_to(jnp.arange(skv), (b, skv))
    return attention_chunked(q, k, v, qpos, kpos, causal=causal, window=window)


def _fwd(q, k, v, causal, window, interpret):
    return _fa_dif(q, k, v, causal, window, interpret), (q, k, v)


def _bwd(causal, window, interpret, res, cot):
    q, k, v = res
    _, vjp = jax.vjp(lambda *a: _ref(*a, causal, window), q, k, v)
    return vjp(cot)


_fa_dif.defvjp(_fwd, _bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(B,S,H,D) flash attention.  Contiguous positions assumed (the model
    only routes full-sequence train/prefill here; decode and ring-buffer
    caches use the chunked JAX path)."""
    return _fa_dif(q, k, v, causal, window, resolve_interpret(interpret))
