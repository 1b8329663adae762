"""Aggregation strategies: weighted FedAvg, delta aggregation, FedBuff-style
asynchronous buffered aggregation with staleness discounting.

All tree arithmetic is dtype-preserving and sharding-preserving (pure
``jax.tree.map`` over the parameter pytree), so the same code path serves
the CPU FL experiments and pod-scale sharded parameters.  FedAvg's weighted
sum runs on the device for every caller, in jitted programs that do not
depend on the number of deltas: deltas already on the device are folded
where they are, host deltas are moved to it once.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import Counter
from repro.obs.trace import Tracer, span

PyTree = Any


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: x - y, a, b)


# Deltas one fold program takes at a time.  A short last chunk is padded,
# so the programs are the same for every number of deltas: a round whose
# count of finishers varies builds nothing new.  Each chunk is one dispatch
# (about 1 ms of host time on a v5e), so the chunk is large.
FOLD_CHUNK = 64


@jax.jit
def _fold_chunk(acc: Optional[PyTree], scales: jax.Array,
                trees: Tuple[PyTree, ...]) -> PyTree:
    """``acc + Σ scales[i]·trees[i]``: each tree scaled, then added in
    order, in float32, elementwise only (a ``dot`` at default precision
    would take bf16 passes on the TPU).  ``acc`` is None on a fold's first
    chunk; ``scales`` is traced, so new weights reuse the program."""
    for i, t in enumerate(trees):
        term = jax.tree.map(lambda x, _i=i: x.astype(jnp.float32) * scales[_i], t)
        acc = term if acc is None else jax.tree.map(jnp.add, acc, term)
    return acc


def fedavg(updates: Sequence[Tuple[PyTree, float]]) -> PyTree:
    """Weighted average of parameter pytrees (weights ∝ client sample
    counts), on the device: :func:`_fold_chunk` over ``FOLD_CHUNK`` deltas
    at a time, in client order, with ``sᵢ = float32(wᵢ / Σw)``.  The last
    chunk is padded with its last delta at scale 0, which adds zero (a
    non-finite delta leaves the sum non-finite either way).  Host leaves
    are moved to the device once."""
    total = float(sum(w for _, w in updates))
    assert total > 0
    trees = [t for t, _ in updates]
    if not all(isinstance(a, jax.Array) for a in jax.tree.leaves(trees)):
        trees = [jax.device_put(t) for t in trees]   # a device array stays put
    scales = np.zeros(-(-len(trees) // FOLD_CHUNK) * FOLD_CHUNK, np.float32)
    scales[:len(trees)] = [w / total for _, w in updates]
    acc = None
    for j in range(0, len(trees), FOLD_CHUNK):
        chunk = trees[j:j + FOLD_CHUNK]
        chunk += [chunk[-1]] * (FOLD_CHUNK - len(chunk))
        acc = _fold_chunk(acc, scales[j:j + FOLD_CHUNK], tuple(chunk))
    return jax.tree.map(lambda a, x: a.astype(x.dtype), acc, trees[0])


def apply_deltas(global_params: PyTree, deltas: Sequence[Tuple[PyTree, float]],
                 server_lr: float = 1.0, *, tracer: Optional[Tracer] = None,
                 pid: str = "trainer", h2d: Optional[Counter] = None) -> PyTree:
    """FedAvg in delta form: θ ← θ + η·Σ wᵢ·Δᵢ / Σ wᵢ.  The weighted sum
    and the apply are the ``fold.sum`` and ``fold.apply`` spans (on
    ``tracer``'s ``pid``/``rounds`` track when one is given).  Deltas that
    are host arrays are moved to the device by the sum; their bytes are
    the span's ``h2d_bytes`` and go to ``h2d``."""
    leaves = [a for d, _ in deltas for a in jax.tree.leaves(d)]
    nbytes = sum(a.nbytes for a in leaves)
    host = sum(a.nbytes for a in leaves if not isinstance(a, jax.Array))
    if h2d is not None:
        h2d.inc(host)
    with span("fold.sum", tracer, pid, "rounds", deltas=len(deltas), bytes=nbytes,
              h2d_bytes=host):
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

    def flush(self, global_params: PyTree, *, h2d: Optional[Counter] = None) -> PyTree:
        assert self._buffer
        weighted = []
        for delta, w, r0 in self._buffer:
            stale = max(self.server_round - r0, 0)
            weighted.append((delta, w / (1.0 + stale) ** self.staleness_alpha))
        self._buffer.clear()
        self.server_round += 1
        return apply_deltas(global_params, weighted, self.server_lr, h2d=h2d)
