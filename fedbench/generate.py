"""What every family of models draws its client pool from, and the
participants of the rounds checked after the window.

A family module (``families/<family>.py``) makes a cell's pool from its
traffic mix (``traffic/<mix>.json``), its model configuration
(``configs/<name>.json``) and a seed, with the helpers here: the seed
streams, FedScale's compute budgets, the shard sizes, non-IID labels and
the per-client batch sizes.

Everything is drawn from ``numpy.random.SeedSequence(seed)``, so any whole
number is a valid seed and the same seed gives the same pool.  Every seed
gives the same *sets* of per-client batch sizes (the mix's shares of the
pool, exactly), shard sizes and compute budgets, each in another order, so
the seed changes which client gets which and not how much work the pool
holds.

Shard sizes are the quantiles of a lognormal with the configuration's
mean and standard deviation of samples per client.  Budgets are the
quantiles of FedScale's long-tailed device speeds, the distribution of the
program's ``repro.core.budget.fedscale_budget_distribution``.  Each
client's labels are non-IID: its class shares are drawn from Dir(alpha)
over the classes (Hsu et al., arXiv:1909.06335).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

@dataclass
class Pool:
    """What one run's clients are made of: every array is host numpy.  A
    client's examples are ``x`` (its family's inputs: images, token
    sequences) and ``y`` (one label each)."""

    x: List[np.ndarray]          # per client, (n_c, ...)
    y: List[np.ndarray]          # per client, (n_c,) int32
    batch_sizes: np.ndarray      # (pool,) int
    budgets: np.ndarray          # (pool,) percent of the pool's compute
    data_seeds: np.ndarray       # (pool,) per-client shuffle seeds
    test: Dict[str, np.ndarray]  # {"x": (T, ...), "y": (T,)}
    fed_seed: int                # the trainer's own seed (< 2**31)
    weight_key: int              # seed of the model's initial weights


def seed_streams(seed: int, n: int) -> List[np.random.Generator]:
    """``n`` independent generators from one seed of any size."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(int(seed)).spawn(n)]


def _quantiles(n: int) -> np.ndarray:
    """The ``n`` mid-point quantiles of the standard normal."""
    nd = NormalDist()
    return np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])


def fedscale_budgets(n: int, quantum: int = 5) -> np.ndarray:
    """FedScale's long-tailed device speeds mapped onto (0, 100] percent
    budgets: the quantiles of a lognormal (mean 3.0, sigma 0.6 in log
    space) clipped to [2, 100], quantized to ``quantum`` percent.  The
    same set for every seed, in rising order."""
    raw = np.clip(np.exp(3.0 + 0.6 * _quantiles(n)), 2.0, 100.0)
    return np.minimum(np.maximum(quantum, np.round(raw / quantum) * quantum), 100.0)


def shard_sizes(n: int, mean: float, sd: float) -> np.ndarray:
    """Samples per client: the quantiles of the lognormal with ``mean``
    and ``sd``, rounded, summing to ``round(n * mean)``.  The same set for
    every seed, in rising order."""
    sigma2 = np.log1p((sd / mean) ** 2)
    mu = np.log(mean) - sigma2 / 2
    sizes = np.maximum(1, np.round(np.exp(mu + np.sqrt(sigma2) * _quantiles(n)))).astype(np.int64)
    # the rounding moves the total: take the difference off (or put it
    # on) the largest shards
    diff = int(round(n * mean)) - int(sizes.sum())
    step = 1 if diff > 0 else -1
    for i in range(abs(diff)):
        sizes[n - 1 - (i % n)] += step
    return sizes


def client_labels(rng: np.random.Generator, sizes: np.ndarray, n_classes: int,
                  alpha: float) -> List[np.ndarray]:
    """Label-skewed non-IID shards: each client's class shares are drawn
    from Dir(alpha) over the classes, and its labels from those shares."""
    shares = rng.dirichlet(np.full(n_classes, alpha), size=len(sizes))
    counts = rng.multinomial(sizes, shares)
    classes = np.arange(n_classes, dtype=np.int32)
    return [np.repeat(classes, row) for row in counts]


def batch_assignment(rng: np.random.Generator, mix: Sequence[Sequence[float]],
                     n: int) -> np.ndarray:
    """Exactly ``round(share * n)`` clients per batch size (the last size
    takes the remainder), shuffled."""
    sizes = [int(b) for b, _ in mix]
    counts = [int(round(float(s) * n)) for _, s in mix[:-1]]
    counts.append(n - sum(counts))
    out = np.repeat(np.asarray(sizes, np.int64), counts)
    rng.shuffle(out)
    return out


def composition_range(traffic: Dict[str, Any], sigmas: float = 4.5) -> List[Tuple[int, ...]]:
    """Per-round batch-size compositions a round can plausibly draw: the
    count of each size among the participants, within ``sigmas`` standard
    deviations of the hypergeometric draw from the pool (two sizes only;
    a single size has one composition)."""
    mix = traffic["batch_mix"]
    k = int(traffic["participants_per_round"])
    if len(mix) == 1:
        return [(k,)]
    if len(mix) != 2:
        raise ValueError("composition_range handles one or two batch sizes")
    n = int(traffic["pool_clients"])
    first = int(round(float(mix[0][1]) * n))
    p = first / n
    mean = k * p
    sd = np.sqrt(k * p * (1 - p) * (n - k) / max(n - 1, 1))
    lo = max(0, int(np.floor(mean - sigmas * sd)), k - (n - first))
    hi = min(k, int(np.ceil(mean + sigmas * sd)), first)
    return [(a, k - a) for a in range(lo, hi + 1)]


def check_participants(seed: int, traffic: Dict[str, Any],
                       batch_sizes: np.ndarray) -> List[np.ndarray]:
    """The participants of the rounds checked after the window: one round
    at each end of :func:`composition_range` (one round where the mix has
    one composition), that many clients of each batch size, drawn from the
    seed and shuffled."""
    rng = seed_streams(seed, 7)[6]
    sizes = [int(b) for b, _ in traffic["batch_mix"]]
    comps = composition_range(traffic)
    out = []
    for comp in dict.fromkeys((comps[0], comps[-1])):
        ids = np.concatenate([rng.choice(np.flatnonzero(batch_sizes == b), k, replace=False)
                              for b, k in zip(sizes, comp)])
        rng.shuffle(ids)
        out.append(ids)
    return out
