"""Aggregation strategies: weighted FedAvg, delta aggregation, FedBuff-style
asynchronous buffered aggregation with staleness discounting.

All tree arithmetic is dtype-preserving and sharding-preserving (pure
``jax.tree.map`` over the parameter pytree), so the same code path serves
the CPU FL experiments and pod-scale sharded parameters.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.trace import Tracer, span

PyTree = Any


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: x + y, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: x - y, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * s).astype(x.dtype), a)


def tree_zeros_like(a: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, a)


def fedavg(updates: Sequence[Tuple[PyTree, float]]) -> PyTree:
    """Weighted average of parameter pytrees (weights ∝ client sample counts)."""
    total = float(sum(w for _, w in updates))
    assert total > 0
    acc = tree_scale(updates[0][0], updates[0][1] / total)
    for params, w in updates[1:]:
        acc = tree_add(acc, tree_scale(params, w / total))
    return acc


def apply_deltas(global_params: PyTree, deltas: Sequence[Tuple[PyTree, float]],
                 server_lr: float = 1.0, *, tracer: Optional[Tracer] = None,
                 pid: str = "trainer") -> PyTree:
    """FedAvg in delta form: θ ← θ + η·Σ wᵢ·Δᵢ / Σ wᵢ.  The weighted sum
    and the apply are the ``fold.sum`` and ``fold.apply`` spans (on
    ``tracer``'s ``pid``/``rounds`` track when one is given)."""
    nbytes = sum(a.nbytes for d, _ in deltas for a in jax.tree.leaves(d))
    with span("fold.sum", tracer, pid, "rounds", deltas=len(deltas), bytes=nbytes):
        avg_delta = fedavg(deltas)
    with span("fold.apply", tracer, pid, "rounds"):
        return jax.tree.map(
            lambda p, d: (p.astype(jnp.float32)
                          + server_lr * d.astype(jnp.float32)).astype(p.dtype),
            global_params,
            avg_delta,
        )


@dataclass
class AsyncAggregator:
    """FedBuff-style buffered async aggregation.

    Clients report (delta, weight, round_started); the buffer flushes every
    ``buffer_size`` arrivals with staleness discount w/(1+s)^alpha — the
    straggler-mitigation path: slow clients never block the round clock.
    """

    buffer_size: int = 8
    staleness_alpha: float = 0.5
    server_lr: float = 1.0
    _buffer: List[Tuple[PyTree, float, int]] = field(default_factory=list)
    server_round: int = 0

    def add(self, delta: PyTree, weight: float, round_started: int) -> bool:
        self._buffer.append((delta, weight, round_started))
        return len(self._buffer) >= self.buffer_size

    def flush(self, global_params: PyTree) -> PyTree:
        assert self._buffer
        weighted = []
        for delta, w, r0 in self._buffer:
            stale = max(self.server_round - r0, 0)
            weighted.append((delta, w / (1.0 + stale) ** self.staleness_alpha))
        self._buffer.clear()
        self.server_round += 1
        return apply_deltas(global_params, weighted, self.server_lr)
