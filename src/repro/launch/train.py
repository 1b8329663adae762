"""Cross-silo federated LM pretraining driver — FedHC at pod scale.

Silos (clients) hold disjoint token-stream shards and heterogeneous resource
budgets; each round the FedHC engine (double-pointer scheduler + dynamic
executor manager + sharing) packs silos onto the resource pool and produces
the round clock, while real local training steps run for every scheduled
silo.  Deltas aggregate with weighted FedAvg (optional int8 uplink
compression); checkpoints are atomic + resumable.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
        --rounds 3 --silos 4 --local-steps 4 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.registry import get_config
from repro.core.aggregation import apply_deltas, tree_sub
from repro.core.budget import fedscale_budget_distribution
from repro.core.runtime import MeasuredRuntime
from repro.core.scheduler import FedHCScheduler
from repro.core.simulator import RoundSimulator, SimClient
from repro.data.pipeline import TokenDataset
from repro.data.synthetic import make_lm_tokens
from repro.fed.compression import compress, compressed_bytes, decompress
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import make_train_step, model_fns


def build_silos(n: int, vocab: int, seq: int, batch: int, seed: int = 0):
    budgets = fedscale_budget_distribution(max(n * 3, 30), seed=seed)[: n]
    silos = []
    for i in range(n):
        tokens = make_lm_tokens(200_000, vocab, seed=seed * 100 + i)
        silos.append({
            "id": i,
            "budget": budgets[i].budget,
            "data": TokenDataset(tokens, seq, batch, seed=seed + i),
        })
    return silos


def main(argv: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Run the campaign; returns what it printed (losses, compile time)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-host scale)")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--silos", type=int, default=4)
    ap.add_argument("--participants", type=int, default=0, help="0 = all silos")
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--theta", type=float, default=100.0)
    ap.add_argument("--compression", default="none", choices=["none", "int8", "topk"])
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.arch == "qwen-100m":
        # ~100M-param pretraining config for the end-to-end example
        cfg = get_config("qwen1.5-0.5b").replace(
            name="qwen-100m", d_model=512, n_heads=8, n_kv_heads=8, d_ff=1408,
            groups=(), n_layers=8, loss_chunk=64, remat="none",
        )
    else:
        cfg = get_config(args.arch, reduced=args.reduced)
    fns = model_fns(cfg)
    train_step, opt = make_train_step(cfg)

    params, _ = fns.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M silos={args.silos}")

    silos = build_silos(args.silos, cfg.vocab_size, args.seq, args.batch)
    runtime = MeasuredRuntime()
    ckpt = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start_round = 0
    if ckpt:
        step0, params = ckpt.restore_latest(params)
        start_round = step0 or 0

    # the silo's params and optimizer state are donated: an undonated step
    # keeps a second copy of both alive, which a published-width model
    # cannot afford next to the global params and the round's deltas
    t0 = time.perf_counter()
    jstep = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        params, jax.eval_shape(opt.init, params),
        {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)},
    ).compile()
    compile_s = time.perf_counter() - t0
    print(f"compile_s={compile_s:.2f}", flush=True)

    comm = 0
    clock = 0.0
    n_part = args.participants or args.silos
    rng = np.random.default_rng(0)
    losses: List[float] = []
    first_loss = None
    for rnd in range(start_round, start_round + args.rounds):
        t0 = time.time()
        chosen = [silos[i] for i in rng.choice(args.silos, size=n_part, replace=False)]
        # framework-provided runtime → round timing via the FedHC engine
        works = {}
        for s in chosen:
            batch = {k: jnp.asarray(v) for k, v in s["data"].next_batch().items()}
            works[s["id"]] = runtime.seconds_at_full(
                (cfg.name, args.batch, args.seq),
                lambda p, b: train_step(p, opt.init(p), b)[0],
                (params, batch), n_steps=args.local_steps,
            )
        sim, _ = RoundSimulator(FedHCScheduler, theta=args.theta).run(
            [SimClient(s["id"], s["budget"], works[s["id"]]) for s in chosen]
        )
        clock += sim.duration

        # real local training
        deltas = []
        last_loss = float("nan")
        for s in chosen:
            local = jax.tree.map(jnp.copy, params)  # the step donates it
            opt_state = opt.init(local)
            for _ in range(args.local_steps):
                batch = {k: jnp.asarray(v) for k, v in s["data"].next_batch().items()}
                local, opt_state, metrics = jstep(local, opt_state, batch)
                if first_loss is None:
                    first_loss = float(metrics["loss"])
            delta = tree_sub(local, params)
            del local, opt_state
            if args.compression != "none":
                c = compress(delta, args.compression, seed=rnd)
                comm += compressed_bytes(c)
                delta = decompress(c)
            else:
                comm += sum(x.nbytes for x in jax.tree.leaves(delta))
            deltas.append((delta, float(args.local_steps * args.batch)))
            last_loss = float(metrics["loss"])
        params = apply_deltas(params, deltas)
        losses.append(last_loss)
        print(
            f"round {rnd+1}: loss={last_loss:.4f} sim_round_s={sim.duration:.2f} "
            f"sim_clock_s={clock:.2f} wall_s={time.time()-t0:.1f} comm_MB={comm/1e6:.1f}",
            flush=True,
        )
        if ckpt:
            ckpt.save(rnd + 1, params, {"sim_clock": clock})
    print("done.")
    return {"arch": cfg.name, "params": n_params, "compile_s": compile_s,
            "first_loss": first_loss, "losses": losses}


if __name__ == "__main__":
    main()
