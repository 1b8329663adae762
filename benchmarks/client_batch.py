"""Client-batched execution benchmark: sequential vs one-program waves.

Times a full COLLECT wave — every participant's local training for one
round — on the sequential path (one jitted step per client per batch,
the pre-batching trainer behaviour) vs ``repro.fed.batch_exec``'s
``BatchedExecutor`` (the whole wave as ONE compiled program), at
8 / 64 / 256 clients, plus a ragged cell where per-client batch sizes
differ and the wave runs through the ``grouped_matmul`` kernel path.

Both paths are fed *twin worlds* built from the same seeds, so the
per-client updated params must match: bit-identical on the dense vmap
path, allclose (documented tolerance, matmul summation order) on the
ragged path.  The params check is part of ``--check``, not just the
speedup floors.

The win on a 1-core CPU host is dispatch amortization: the sequential
path pays Python + jit-call overhead ``clients x steps`` times per
round, the batched path once per wave.  (On real accelerator meshes the
wave additionally spreads over devices via ``shard_map``.)  The model is
deliberately small — FL client workloads are edge-device sized, which is
exactly the dispatch-bound regime FL simulators live in (FedML Parrot
makes the same observation).

Headline criteria (asserted by ``--check``, run by the CI clients-bench
job):

* ``speedup_64``  >= 5.0 full / >= 2.0 quick — wall-clock, one 64-client
  round, batched vs sequential (quick floor is lower: CI runners are
  shared and noisy, and quick mode runs fewer local steps so fixed
  per-wave costs amortize less);
* ``ragged_speedup_64`` >= 1.5 — the grouped-matmul ragged wave must
  also beat sequential, not just the uniform vmap wave;
* ``params_max_abs_diff`` <= 1e-5 — batched per-client updated params
  match sequential per-client params across every cell;
* ``cache_hit_waves`` — every wave after a cell's first must hit the
  compiled-program cache (no silent per-wave recompilation).

Usage::

    PYTHONPATH=src python benchmarks/client_batch.py           # full run
    PYTHONPATH=src python benchmarks/client_batch.py --quick --check  # CI
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, List

import numpy as np

import jax

from repro.core.budget import WorkloadSpec
from repro.data.pipeline import ClientDataset
from repro.fed.batch_exec import BatchedExecutor
from repro.fed.client import FLClient, make_small_step, step_cache_stats
from repro.models.small import SmallModelConfig, init_small
from repro.optim.optimizers import make_optimizer

MCFG = SmallModelConfig(kind="mlp", hidden=16, n_layers=2, image_size=8,
                        channels=1, n_classes=10)


def build_world(n_clients: int, batch_sizes, seed: int):
    """A fresh FL world: per-client shards + the shared global params.
    Called twice with the same seed per measurement so the sequential and
    batched runs consume identical data-pipeline RNG state."""
    rng = np.random.default_rng(seed)
    clients = []
    for i in range(n_clients):
        bs = batch_sizes[i % len(batch_sizes)]
        n = max(4 * bs, 8)
        x = rng.normal(size=(n, MCFG.image_size, MCFG.image_size,
                             MCFG.channels)).astype(np.float32)
        y = rng.integers(0, MCFG.n_classes, size=n).astype(np.int32)
        clients.append(FLClient(i, 100.0, ClientDataset(x, y, bs, seed=seed + i),
                                WorkloadSpec()))
    params = init_small(jax.random.PRNGKey(seed), MCFG)
    return clients, params


def run_sequential(clients, params, opt, steps: int):
    step = make_small_step(MCFG, opt, 0.0)
    return [c.train_local(params, step, opt, n_steps=steps) for c in clients]


def _max_abs_diff(res_a, res_b) -> float:
    return max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for (da, _, _), (db, _, _) in zip(res_a, res_b)
        for a, b in zip(jax.tree.leaves(da), jax.tree.leaves(db))
    )


def bench_cell(name: str, n_clients: int, batch_sizes, steps: int,
               reps: int, opt) -> Dict[str, Any]:
    """One (cell, client-count) measurement: best-of-``reps`` wall time
    per path, params-match on the last rep, executor cache stats."""
    ex = BatchedExecutor(MCFG, opt, 0.0)
    # warmup: compile both paths outside the timed region
    cl, params = build_world(n_clients, batch_sizes, seed=0)
    run_sequential(cl, params, opt, steps)
    cl, params = build_world(n_clients, batch_sizes, seed=0)
    ex.run_wave(params, cl, steps, round_idx=0)

    best_seq = best_bat = float("inf")
    seq_res = bat_res = None
    for rep in range(reps):
        cl, params = build_world(n_clients, batch_sizes, seed=1 + rep)
        t0 = time.perf_counter()
        seq_res = run_sequential(cl, params, opt, steps)
        jax.block_until_ready([d for d, _, _ in seq_res])
        best_seq = min(best_seq, time.perf_counter() - t0)

        cl, params = build_world(n_clients, batch_sizes, seed=1 + rep)
        t0 = time.perf_counter()
        bat_res = ex.run_wave(params, cl, steps, round_idx=1 + rep)
        jax.block_until_ready([d for d, _, _ in bat_res])
        best_bat = min(best_bat, time.perf_counter() - t0)

    stats = ex.stats.as_dict()
    return {
        "cell": name,
        "clients": n_clients,
        "steps": steps,
        "batch_sizes": sorted(set(batch_sizes)),
        "mode": ex.last_wave.get("mode"),
        "seq_s": best_seq,
        "bat_s": best_bat,
        "speedup": best_seq / best_bat,
        "params_max_abs_diff": _max_abs_diff(seq_res, bat_res),
        "waves": stats["waves"],
        "compiles": stats["compiles"],
        "cache_hits": stats["cache_hits"],
    }


def run(quick: bool = False) -> Dict[str, Any]:
    steps = 10 if quick else 25
    reps = 2 if quick else 3
    opt = make_optimizer("sgd", 0.05)
    cells: List[Dict[str, Any]] = []
    plan = [
        ("dense_8", 8, [4]),
        ("dense_64", 64, [4]),
        ("dense_256", 256, [4]),
        ("ragged_64", 64, [2, 4, 6, 8]),
    ]
    for name, n, bss in plan:
        cell = bench_cell(name, n, bss, steps, reps, opt)
        cells.append(cell)
        print(f"{name:>10s}: C={n:3d} mode={cell['mode']:>6s}  "
              f"seq {cell['seq_s']*1e3:7.1f}ms  bat {cell['bat_s']*1e3:6.1f}ms  "
              f"{cell['speedup']:5.2f}x  max|d|={cell['params_max_abs_diff']:.1e}  "
              f"compiles={cell['compiles']} hits={cell['cache_hits']}",
              flush=True)

    by = {c["cell"]: c for c in cells}
    headline = {
        "speedup_8": by["dense_8"]["speedup"],
        "speedup_64": by["dense_64"]["speedup"],
        "speedup_256": by["dense_256"]["speedup"],
        "ragged_speedup_64": by["ragged_64"]["speedup"],
        "params_max_abs_diff": max(c["params_max_abs_diff"] for c in cells),
        # waves past each cell's first (the warmup compile) must hit the
        # program cache — 1.0 means no per-wave recompilation anywhere
        "cache_hit_waves": (
            sum(c["cache_hits"] for c in cells)
            / max(sum(c["waves"] - c["compiles"] for c in cells), 1)
        ),
        "step_cache": step_cache_stats(),
    }
    print("\nheadline:")
    for k, v in headline.items():
        print(f"  {k:>20s}: {v}")
    return {
        "bench": "client_batch",
        "quick": quick,
        "model": {"kind": MCFG.kind, "hidden": MCFG.hidden,
                  "n_layers": MCFG.n_layers,
                  "in_dim": MCFG.image_size ** 2 * MCFG.channels},
        "cells": cells,
        "headline": headline,
        "thresholds": {
            "speedup_64": 2.0 if quick else 5.0,
            "ragged_speedup_64": 1.5,
            "cache_hit_waves": 1.0,
        },
        "tolerances": {"params_max_abs_diff": 1e-5},
    }


def check(report: Dict[str, Any]) -> List[str]:
    fails = []
    for key, floor in report["thresholds"].items():
        got = report["headline"][key]
        if got < floor:
            fails.append(f"{key} = {got:.2f} < required {floor}")
    for key, ceil in report["tolerances"].items():
        got = report["headline"][key]
        if got > ceil:
            fails.append(f"{key} = {got:.2e} > allowed {ceil}")
    return fails


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI scale: fewer local steps and reps")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero if a headline threshold is missed")
    ap.add_argument("--out", default="BENCH_clients.json")
    args = ap.parse_args()
    report = run(quick=args.quick)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"\nwrote {args.out}")
    if args.check:
        fails = check(report)
        for f_ in fails:
            print(f"THRESHOLD MISS: {f_}")
        return 1 if fails else 0
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
