"""Dropless token-choice top-k Mixture-of-Experts (OLMoE / Kimi-K2 style).

Dispatch is MegaBlocks-style: flatten tokens, replicate ×k, stable-sort by
expert id, run three grouped GEMMs (`lax.ragged_dot`, or the Pallas
``grouped_matmul`` kernel on TPU), unsort, and combine with renormalized
router weights.  No capacity factor, no token dropping.

Distribution: routing/sort must stay *local* to each data shard (a global
argsort under SPMD would all-gather the token stream), so the sharded path
wraps the local computation in ``shard_map``:

* tokens:   split over the batch axes ("pod","data")
* experts:  weights split over batch axes too (ZeRO-3) — all-gathered just
            before use, gradients reduce-scattered by autodiff transpose
* d_ff:     split over "model" (TP inside each expert); the down-projection
            produces partial sums reduced with ``psum("model")``

The router is replicated; its gradient is psum-reduced by shard_map.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.grouped_matmul import ops as gmm_ops

Params = Dict[str, Any]


def init_moe(key: jax.Array, cfg: ModelConfig) -> Tuple[Params, Params]:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    pd = jnp.dtype(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    out_std = 0.02 / math.sqrt(2.0 * max(cfg.total_layers, 1))
    params = {
        "router": (jax.random.normal(k1, (d, e)) * std).astype(jnp.float32),
        "wg": (jax.random.normal(k2, (e, d, f)) * std).astype(pd),
        "wu": (jax.random.normal(k3, (e, d, f)) * std).astype(pd),
        "wd": (jax.random.normal(k4, (e, f, d)) * out_std).astype(pd),
    }
    axes = {
        "router": ("embed", None),
        # "expert_embed" (not "embed") so the d_model dim never steals the
        # ZeRO-3 data axis from "expert_mlp" during per-tensor dedup
        "wg": ("expert", "expert_embed", "expert_mlp"),
        "wu": ("expert", "expert_embed", "expert_mlp"),
        "wd": ("expert", "expert_mlp", "expert_embed"),
    }
    return params, axes


# --------------------------------------------------------------------------
# Local (per-shard) computation
# --------------------------------------------------------------------------


def route(router_w: jax.Array, x_flat: jax.Array, cfg: ModelConfig):
    """Return (top_probs (T,k), top_idx (T,k), probs (T,E))."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i, probs


def _moe_local(
    router_w: jax.Array,
    wg: jax.Array,
    wu: jax.Array,
    wd: jax.Array,
    x: jax.Array,
    cfg: ModelConfig,
    gmm_impl: str = "ragged",
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) local tokens.  Returns (out (B,S,D), aux loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cd = jnp.dtype(cfg.compute_dtype)
    t = b * s
    xf = x.reshape(t, d)

    top_p, top_i, probs = route(router_w, xf, cfg)

    flat_e = top_i.reshape(-1)                       # (t*k,)
    sort_idx = jnp.argsort(flat_e)                   # stable
    tok_idx = sort_idx // k                          # source token per row
    xs = jnp.take(xf, tok_idx, axis=0).astype(cd)    # (t*k, d)
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)

    g = gmm_ops.grouped_matmul(xs, wg.astype(cd), group_sizes, impl=gmm_impl)
    u = gmm_ops.grouped_matmul(xs, wu.astype(cd), group_sizes, impl=gmm_impl)
    h = jax.nn.silu(g) * u
    ys = gmm_ops.grouped_matmul(h, wd.astype(cd), group_sizes, impl=gmm_impl)

    gates = jnp.take(top_p.reshape(-1), sort_idx, axis=0).astype(jnp.float32)
    contrib = ys.astype(jnp.float32) * gates[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[tok_idx].add(contrib)

    # Switch-style load-balancing auxiliary loss.
    frac = group_sizes.astype(jnp.float32) / jnp.maximum(t * k, 1)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return out.reshape(b, s, d).astype(x.dtype), aux


# --------------------------------------------------------------------------
# Sharded computation
# --------------------------------------------------------------------------


def _moe_shard_body(router_w, wg, wu, wd, x, *, cfg: ModelConfig, fsdp_axes, gmm_impl):
    """ZeRO-3 "gather" impl: experts sharded over the batch axes at rest,
    all-gathered before use (gradients reduce-scatter via transpose); d_ff
    is tensor-parallel over the model axis."""
    if fsdp_axes:
        wg = lax.all_gather(wg, fsdp_axes, axis=0, tiled=True)
        wu = lax.all_gather(wu, fsdp_axes, axis=0, tiled=True)
        wd = lax.all_gather(wd, fsdp_axes, axis=0, tiled=True)
    out, aux = _moe_local(router_w, wg, wu, wd, x, cfg, gmm_impl)
    out = lax.psum(out, "model")
    axes = tuple(fsdp_axes) + ("model",) if fsdp_axes else ("model",)
    aux = lax.pmean(aux, axes)
    return out, aux


def _moe_shard_body_ep(
    router_w, wg, wu, wd, x, *, cfg: ModelConfig, fsdp_axes, gmm_impl, n_model: int
):
    """Expert-parallel impl: each model shard OWNS E/n_model experts (the
    full expert stack is never materialized on one device), selects the rows
    routed to its experts up to a static per-shard capacity, and psums the
    partial outputs over the model axis.

    Routing is computed redundantly per shard (tokens are replicated over
    the model axis inside this block) so no token all-to-all is required —
    a TPU-friendly EP formulation; overflow beyond capacity is dropped and
    reported, standard EP behavior.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = wg.shape[0]
    cd = jnp.dtype(cfg.compute_dtype)
    if fsdp_axes:  # ZeRO-3 on the per-expert FFN dim
        wg = lax.all_gather(wg, fsdp_axes, axis=2, tiled=True)
        wu = lax.all_gather(wu, fsdp_axes, axis=2, tiled=True)
        wd = lax.all_gather(wd, fsdp_axes, axis=1, tiled=True)
    f = wg.shape[2]
    # zero "trash" expert: rows beyond this shard's load land there
    wg_p = jnp.concatenate([wg, jnp.zeros((1, d, f), wg.dtype)], axis=0)
    wu_p = jnp.concatenate([wu, jnp.zeros((1, d, f), wu.dtype)], axis=0)
    wd_p = jnp.concatenate([wd, jnp.zeros((1, f, d), wd.dtype)], axis=0)

    m_idx = lax.axis_index("model")
    t = b * s
    xf = x.reshape(t, d)
    nc = max(1, min(cfg.moe_token_chunks, t))
    tc = t // nc  # tokens per chunk (t is a multiple of S which is pow2-ish)
    cap = int(cfg.moe_ep_capacity * tc * k / max(n_model, 1))
    cap = max(min(cap, tc * k), 1)

    def chunk_body(xc):
        """EP dispatch for one token chunk (bounds the dispatch buffers)."""
        top_p, top_i, probs = route(router_w, xc, cfg)
        flat_e = top_i.reshape(-1)                                  # (tc·k,)
        local = (flat_e // e_loc) == m_idx
        sort_key = jnp.where(local, flat_e - m_idx * e_loc, e_loc)  # sentinel last
        order = jnp.argsort(sort_key)
        take = order[:cap]
        rel_e = jnp.take(sort_key, take, axis=0)                    # in [0, e_loc]
        valid = rel_e < e_loc

        counts = jnp.bincount(rel_e, length=e_loc + 1)
        group_sizes = counts.at[e_loc].set(
            cap - jnp.sum(counts[:e_loc])
        ).astype(jnp.int32)

        tok_idx = take // k
        xs = jnp.take(xc, tok_idx, axis=0).astype(cd)
        g = gmm_ops.grouped_matmul(xs, wg_p.astype(cd), group_sizes, impl=gmm_impl)
        u = gmm_ops.grouped_matmul(xs, wu_p.astype(cd), group_sizes, impl=gmm_impl)
        h = jax.nn.silu(g) * u
        ys = gmm_ops.grouped_matmul(h, wd_p.astype(cd), group_sizes, impl=gmm_impl)

        gates = jnp.take(top_p.reshape(-1), take, axis=0).astype(cd)
        gates = gates * valid.astype(cd)
        contrib = ys.astype(cd) * gates[:, None]
        out_c = jnp.zeros((tc, d), jnp.float32).at[tok_idx].add(
            contrib.astype(jnp.float32)
        )
        frac = jnp.bincount(flat_e, length=e).astype(jnp.float32) / jnp.maximum(tc * k, 1)
        aux_c = e * jnp.sum(frac * jnp.mean(probs, axis=0))
        return out_c, aux_c

    if nc == 1:
        out, aux = chunk_body(xf)
    else:
        outs, auxs = lax.map(chunk_body, xf.reshape(nc, tc, d))
        out, aux = outs.reshape(t, d), jnp.mean(auxs)

    out = lax.psum(out, "model").astype(x.dtype).reshape(b, s, d)
    if fsdp_axes:
        aux = lax.pmean(aux, tuple(fsdp_axes))
    return out, aux


def _moe_shard_body_ep_resident(
    router_w, wg, wu, wd, x, *, cfg: ModelConfig, fsdp_axes, gmm_impl, n_model: int
):
    """Decode-time EP with RESIDENT weights: never all-gathers the experts.

    Expert weights stay 2-D sharded (experts over "model", per-expert d_ff
    over the batch axes); the few decode tokens are all-gathered instead
    (KBs vs the 10s-of-GB weight gather), every shard computes its (expert,
    f-slice) partial for ALL tokens, and one psum over (model + batch axes)
    assembles the outputs — the weight-movement collective disappears from
    the serve step entirely."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = wg.shape[0]
    f_loc = wg.shape[2]
    cd = jnp.dtype(cfg.compute_dtype)

    wg_p = jnp.concatenate([wg, jnp.zeros((1, d, f_loc), wg.dtype)], axis=0)
    wu_p = jnp.concatenate([wu, jnp.zeros((1, d, f_loc), wu.dtype)], axis=0)
    wd_p = jnp.concatenate([wd, jnp.zeros((1, f_loc, d), wd.dtype)], axis=0)

    if fsdp_axes:
        xg = lax.all_gather(x, fsdp_axes, axis=0, tiled=True)  # (B_full, s, d)
    else:
        xg = x
    t = xg.shape[0] * s
    xf = xg.reshape(t, d)

    m_idx = lax.axis_index("model")
    top_p, top_i, probs = route(router_w, xf, cfg)
    flat_e = top_i.reshape(-1)
    local = (flat_e // e_loc) == m_idx
    sort_key = jnp.where(local, flat_e - m_idx * e_loc, e_loc)
    order = jnp.argsort(sort_key)
    cap = max(min(int(cfg.moe_ep_capacity * t * k / max(n_model, 1)), t * k), 1)
    take = order[:cap]
    rel_e = jnp.take(sort_key, take, axis=0)
    valid = rel_e < e_loc
    counts = jnp.bincount(rel_e, length=e_loc + 1)
    group_sizes = counts.at[e_loc].set(cap - jnp.sum(counts[:e_loc])).astype(jnp.int32)

    tok_idx = take // k
    xs = jnp.take(xf, tok_idx, axis=0).astype(cd)
    g = gmm_ops.grouped_matmul(xs, wg_p.astype(cd), group_sizes, impl=gmm_impl)
    u = gmm_ops.grouped_matmul(xs, wu_p.astype(cd), group_sizes, impl=gmm_impl)
    h = jax.nn.silu(g) * u
    ys = gmm_ops.grouped_matmul(h, wd_p.astype(cd), group_sizes, impl=gmm_impl)

    gates = jnp.take(top_p.reshape(-1), take, axis=0).astype(cd) * valid.astype(cd)
    out_full = jnp.zeros((t, d), jnp.float32).at[tok_idx].add(
        (ys.astype(cd) * gates[:, None]).astype(jnp.float32)
    )
    psum_axes = ("model",) + tuple(fsdp_axes)
    out_full = lax.psum(out_full, psum_axes)
    if fsdp_axes:
        idx = jnp.int32(0)
        stride = 1
        for a in reversed(fsdp_axes):
            idx = idx + lax.axis_index(a) * stride
            stride = stride * lax.axis_size(a)
        out = lax.dynamic_slice_in_dim(out_full.reshape(-1, s, d), idx * b, b, axis=0)
    else:
        out = out_full.reshape(b, s, d)
    return out.astype(x.dtype), jnp.zeros((), jnp.float32)


def moe_ffn(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    mesh: Optional[Mesh] = None,
    gmm_impl: str = "ragged",
    resident: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Mixture-of-experts FFN.  (B,S,D) -> ((B,S,D), aux-loss scalar)."""
    if mesh is None or mesh.devices.size == 1:
        return _moe_local(
            params["router"], params["wg"], params["wu"], params["wd"], x, cfg, gmm_impl
        )
    b_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp_axes = b_axes if cfg.fsdp_params else ()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes.get("model", 1)
    if cfg.moe_impl == "ep" and n_model > 1 and cfg.n_experts % n_model == 0:
        w_spec = P("model", None, fsdp_axes if fsdp_axes else None)
        wd_spec = P("model", fsdp_axes if fsdp_axes else None, None)
        ep_body = _moe_shard_body_ep_resident if resident else _moe_shard_body_ep
        body = partial(
            ep_body, cfg=cfg, fsdp_axes=fsdp_axes, gmm_impl=gmm_impl,
            n_model=n_model,
        )
    else:
        w_spec = P(fsdp_axes if fsdp_axes else None, None, "model")
        wd_spec = P(fsdp_axes if fsdp_axes else None, "model", None)
        body = partial(_moe_shard_body, cfg=cfg, fsdp_axes=fsdp_axes, gmm_impl=gmm_impl)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, None), w_spec, w_spec, wd_spec, P(b_axes, None, None)),
        out_specs=(P(b_axes, None, None), P()),
        check_vma=False,
    )
    return fn(params["router"], params["wg"], params["wu"], params["wd"], x)
