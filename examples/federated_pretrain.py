"""End-to-end driver: federated pretraining of a ~100M-parameter LM.

Four silos with heterogeneous budgets train a qwen-family ~100M config on
disjoint Zipf token shards; FedHC schedules each round, real optimizer steps
run per silo, deltas FedAvg into the global model, checkpoints are
resumable.  A few hundred steps ≈
``--rounds 50 --local-steps 4`` (50 rounds × 4 silos × 4 steps = 800 steps).

    PYTHONPATH=src python examples/federated_pretrain.py --rounds 3
    PYTHONPATH=src python examples/federated_pretrain.py --rounds 50   # full run
"""
import argparse

from repro.launch import train as train_mod


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--ckpt-dir", default="/tmp/fedhc_pretrain_ckpt")
    args = ap.parse_args()
    train_mod.main([
        "--arch", "qwen-100m",  # d=512, 8L, vocab 151936 ≈ 103M params
        "--rounds", str(args.rounds),
        "--silos", "4",
        "--local-steps", "4",
        "--batch", "8",
        "--seq", "128",
        "--ckpt-dir", args.ckpt_dir,
    ])


if __name__ == "__main__":
    main()
