"""The image family: FEMNIST-shaped writers for the program's image kinds
(``SmallModelConfig`` kinds ``cnn`` and ``mlp``), the family of every
configuration without a ``"family"`` key.

A family module gives the harness (``run.py``) what depends on the kind
of data and model:

- ``make_pool(seed, traffic, config)``: the client pool
  (``generate.Pool``), from the helpers of ``generate.py``;
- ``build_trainer(spec, pool)``: the clients and the ``FederatedTrainer``
  with the mix's ``FedConfig`` knobs (``run.py`` checks its parameter
  layout against the reference and hands it the benchmark's weights);
- ``warm(tr, traffic, config)``: runs, in set-up, every program the
  window can build that the checked rounds did not; returns how many;
- ``example_bytes(m)``: one example as the program feeds it
  (``flops.StepCounts``);
- ``STEP_PROGRAMS``: the names of the local-step programs in the trace
  (``metrics/train_roofline.py``).

Here the images are class-conditional Gaussians over a rank-32 basis,
the shape of the program's synthetic FEMNIST, made on the device in
fixed-size chunks, one jitted call each, pulled to the host chunk by
chunk, so that set-up never holds more than :data:`CHUNK_ROWS` images on
the device.  Shard sizes follow LEAF FEMNIST's mean and standard
deviation of samples per writer, scaled to the configuration's mean.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

from generate import (Pool, batch_assignment, client_labels, composition_range,
                      fedscale_budgets, seed_streams, shard_sizes)

#: rank of the class-mean basis and the class separation of the images
IMAGE_RANK = 32
CLASS_SEP = 8.0
#: images drawn in one device call (8192 FEMNIST images are 25.7 MB)
CHUNK_ROWS = 8192
#: the executor's jitted local-step programs as JAX names them: the dense
#: wave (after its per-client function ``one``), the ragged wave, the
#: per-client step
STEP_PROGRAMS = ("jit_one", "jit_wave", "jit_step")


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
    """FEMNIST-shaped writers: label-skewed shards of images, the labels
    read mod the model's classes."""
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


def build_trainer(spec: Dict[str, Any], pool: Pool):
    """The pool's clients and the trainer, with the mix's ``FedConfig``
    knobs and the program's own initial weights."""
    from repro.core.budget import WorkloadSpec
    from repro.data.pipeline import ClientDataset
    from repro.fed.client import FLClient
    from repro.fed.trainer import FedConfig, FederatedTrainer
    from repro.models.small import SmallModelConfig

    traffic = spec["traffic"]
    m = spec["config"]["model"]
    steps = int(traffic["local_steps"])
    clients = [
        FLClient(cid, float(pool.budgets[cid]),
                 ClientDataset(pool.x[cid], pool.y[cid], int(pool.batch_sizes[cid]),
                               seed=int(pool.data_seeds[cid])),
                 WorkloadSpec(model=m["kind"], n_layers=m["n_layers"],
                              batch_size=int(pool.batch_sizes[cid]), n_batches=steps))
        for cid in range(len(pool.x))]
    fed = FedConfig(rounds=1 << 30, participants_per_round=int(traffic["participants_per_round"]),
                    local_steps=steps, seed=pool.fed_seed, **traffic["fed"])
    return FederatedTrainer(SmallModelConfig(**m), clients, fed, test_batch=pool.test)


def warm(tr, traffic: Dict[str, Any], config: Dict[str, Any]) -> int:
    """Where the executor ran a ragged wave, which compiles one program
    per total row count: one wave through the trainer's own executor for
    every per-round composition of batch sizes the pool can plausibly
    draw, so that no program is built inside the window.  Returns the
    waves run."""
    from repro.data.pipeline import ClientDataset
    from repro.fed.client import FLClient

    if tr.batch_exec is None or tr.batch_exec.last_wave.get("mode") != "ragged":
        return 0
    d = config["data"]
    shape = (d["image_size"], d["image_size"], d["channels"])
    sizes = [int(b) for b, _ in traffic["batch_mix"]]
    steps = int(traffic["local_steps"])
    n = 0
    for comp in composition_range(traffic):
        bs = [b for b, k in zip(sizes, comp) for _ in range(k)]
        fakes = [FLClient(i, 50.0, ClientDataset(np.zeros((b,) + shape, np.float32),
                                                 np.zeros((b,), np.int32), b))
                 for i, b in enumerate(bs)]
        tr.batch_exec.run_wave(tr.params, fakes, steps, 0)
        n += 1
    return n


def example_bytes(m: Dict[str, Any], x_bytes: int = 4, y_bytes: int = 4) -> int:
    """One example as the program feeds it: float32 pixels, int32 label."""
    return m["image_size"] * m["image_size"] * m["channels"] * x_bytes + y_bytes
