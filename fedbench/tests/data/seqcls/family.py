"""A family for the harness's tests only: sentence classification with the
program's LSTM (``SmallModelConfig(kind="lstm")``), as in FedHC's SST-2
experiments, on sequences of token ids with one label each.

The test copies this file to ``fedbench/families/seqcls.py`` of a
checkout's copy, to show that a family enters the harness by new files
alone.  Its interface is the image family's (``families/image.py``).
Each class draws most of its tokens from its own slice of the
vocabulary, the rest uniformly.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from generate import Pool, batch_assignment, client_labels, fedscale_budgets, seed_streams, shard_sizes

#: the per-client step of the sequential path, the only one this family runs
STEP_PROGRAMS = ("jit_step",)
#: share of a sequence's tokens drawn from its class's slice of the vocabulary
OWN_SHARE = 0.7


def make_tokens(rng: np.random.Generator, labels: np.ndarray, m: Dict[str, Any]) -> np.ndarray:
    n, s, v = len(labels), int(m["seq_len"]), int(m["vocab_size"])
    width = v // int(m["n_classes"])
    own = labels[:, None] * width + rng.integers(0, width, (n, s))
    other = rng.integers(0, v, (n, s))
    return np.where(rng.random((n, s)) < OWN_SHARE, own, other).astype(np.int32)


def make_pool(seed: int, traffic: Dict[str, Any], config: Dict[str, Any]) -> Pool:
    r_lab, r_split, r_batch, r_budget, r_seeds, r_misc = seed_streams(seed, 6)
    m = config["model"]
    n, n_test, classes = int(traffic["pool_clients"]), int(traffic["test_samples"]), int(m["n_classes"])
    sizes = r_split.permutation(shard_sizes(n, float(config["samples_per_client"]),
                                            float(config["samples_per_client_sd"])))
    parts = client_labels(r_split, sizes, classes, float(traffic["dirichlet_alpha"]))
    labels = np.concatenate(parts + [r_lab.integers(0, classes, size=n_test).astype(np.int32)])
    tokens = make_tokens(np.random.default_rng(int(r_misc.integers(2 ** 31))), labels, m)
    n_train = int(sizes.sum())
    cuts = np.cumsum(sizes)[:-1]
    return Pool(
        x=np.split(tokens[:n_train], cuts),
        y=np.split(labels[:n_train], cuts),
        batch_sizes=batch_assignment(r_batch, traffic["batch_mix"], n),
        budgets=r_budget.permutation(fedscale_budgets(n)),
        data_seeds=r_seeds.integers(0, 2 ** 31, size=n),
        test={"x": tokens[n_train:], "y": labels[n_train:]},
        fed_seed=int(r_misc.integers(2 ** 31)),
        weight_key=int(r_misc.integers(2 ** 31)),
    )


def build_trainer(spec: Dict[str, Any], pool: Pool):
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
                 WorkloadSpec(model=m["kind"], n_layers=m["n_layers"], seq_len=m["seq_len"],
                              batch_size=int(pool.batch_sizes[cid]), n_batches=steps))
        for cid in range(len(pool.x))]
    fed = FedConfig(rounds=1 << 30, participants_per_round=int(traffic["participants_per_round"]),
                    local_steps=steps, seed=pool.fed_seed, **traffic["fed"])
    return FederatedTrainer(SmallModelConfig(**m), clients, fed, test_batch=pool.test)


def warm(tr, traffic: Dict[str, Any], config: Dict[str, Any]) -> int:
    """Nothing: one batch size, and the checked rounds run its one step program."""
    return 0


def example_bytes(m: Dict[str, Any]) -> int:
    """int32 token ids and an int32 label."""
    return 4 * int(m["seq_len"]) + 4
