"""The one traffic generator: a client pool and its data, from a traffic
mix (``traffic/<mix>.json``), a model configuration (``configs/<name>.json``)
and a seed.

Everything is drawn from ``numpy.random.SeedSequence(seed)``, so any whole
number is a valid seed and the same seed gives the same pool.  Every seed
gives the same *sets* of per-client batch sizes (the mix's shares of the
pool, exactly), shard sizes and compute budgets, each in another order, so
the seed changes which client gets which and not how much work the pool
holds.

Shard sizes are the quantiles of a lognormal with the configuration's
mean and standard deviation of samples per client (LEAF FEMNIST's, scaled
to the cut mean).  Budgets are the quantiles of FedScale's long-tailed device
speeds, the distribution of the program's
``repro.core.budget.fedscale_budget_distribution``.  Each client's labels
are non-IID: its class shares are drawn from Dir(alpha) over the classes
(Hsu et al., arXiv:1909.06335).  The images are made on the device in
fixed-size chunks, one jitted call each, pulled to the host chunk by
chunk, so that set-up never holds more than :data:`CHUNK_ROWS` images on
the device: class-conditional Gaussians over a rank-32 basis, the shape of
the program's synthetic FEMNIST.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

#: rank of the class-mean basis and the class separation of the images
IMAGE_RANK = 32
CLASS_SEP = 8.0
#: images drawn in one device call (8192 FEMNIST images are 25.7 MB)
CHUNK_ROWS = 8192


@dataclass
class Pool:
    """What one run's clients are made of: every array is host numpy."""

    x: List[np.ndarray]          # per client, (n_c, H, W, C) float32
    y: List[np.ndarray]          # per client, (n_c,) int32
    batch_sizes: np.ndarray      # (pool,) int
    budgets: np.ndarray          # (pool,) percent of the pool's compute
    data_seeds: np.ndarray       # (pool,) per-client shuffle seeds
    test: Dict[str, np.ndarray]  # {"x": (T, H, W, C), "y": (T,)}
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


@functools.lru_cache(maxsize=None)
def _chunk_fn(h: int, c: int, k: int):
    """The jitted draw of one chunk of :data:`CHUNK_ROWS` images."""
    import jax
    import jax.numpy as jnp

    dim = h * h * c

    @jax.jit
    def draw(key, i, y):
        kb, kp, kn = jax.random.split(key, 3)
        basis = jax.random.normal(kb, (k, IMAGE_RANK), jnp.float32)
        proj = jax.random.normal(kp, (IMAGE_RANK, dim), jnp.float32) / np.sqrt(IMAGE_RANK)
        means = (basis @ proj) * (CLASS_SEP / np.sqrt(dim))
        noise = jax.random.normal(jax.random.fold_in(kn, i), (y.shape[0], dim), jnp.float32)
        return (means[y] + noise).reshape(y.shape[0], h, h, c)

    return draw


def make_images(seed: int, labels: np.ndarray, data: Dict[str, int]) -> np.ndarray:
    """Images for ``labels``: class means over a rank-32 basis plus unit
    Gaussian noise, drawn on the default device :data:`CHUNK_ROWS` at a
    time (the last chunk padded) and gathered on the host."""
    import jax

    h, c = data["image_size"], data["channels"]
    draw = _chunk_fn(h, c, data["classes"])
    key = jax.random.key(seed)
    out = np.empty((len(labels), h, h, c), np.float32)
    y = np.zeros(CHUNK_ROWS, np.int32)
    for i, lo in enumerate(range(0, len(labels), CHUNK_ROWS)):
        m = min(CHUNK_ROWS, len(labels) - lo)
        y[:m], y[m:] = labels[lo:lo + m], 0
        out[lo:lo + m] = np.asarray(jax.device_get(draw(key, i, y)))[:m]
    return out


def make_pool(seed: int, traffic: Dict[str, Any], config: Dict[str, Any]) -> Pool:
    r_lab, r_split, r_batch, r_budget, r_seeds, r_misc = seed_streams(seed, 6)
    data = config["data"]
    n = int(traffic["pool_clients"])
    n_test = int(traffic["test_samples"])
    sizes = r_split.permutation(shard_sizes(n, float(config["samples_per_client"]),
                                            float(config["samples_per_client_sd"])))
    parts = client_labels(r_split, sizes, int(data["classes"]),
                          float(traffic["dirichlet_alpha"]))
    labels = np.concatenate(parts + [r_lab.integers(0, data["classes"], size=n_test)
                                     .astype(np.int32)])
    images = make_images(int(r_misc.integers(2 ** 31)), labels, data)
    # the model's classes: a 10-class model reads the FEMNIST writers'
    # labels mod 10, as digits
    classes = labels % int(config["model"]["n_classes"])
    n_train = int(sizes.sum())
    cuts = np.cumsum(sizes)[:-1]
    return Pool(
        x=np.split(images[:n_train], cuts),
        y=np.split(classes[:n_train], cuts),
        batch_sizes=batch_assignment(r_batch, traffic["batch_mix"], n),
        budgets=r_budget.permutation(fedscale_budgets(n)),
        data_seeds=r_seeds.integers(0, 2 ** 31, size=n),
        test={"x": images[n_train:], "y": classes[n_train:]},
        fed_seed=int(r_misc.integers(2 ** 31)),
        weight_key=int(r_misc.integers(2 ** 31)),
    )


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
