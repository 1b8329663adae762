#!/usr/bin/env python3
"""Smoke run of FedHC's two federated training paths on one TPU chip.

    python3 chip_smoke.py

One process, no subprocesses.  Each phase prints what it found as one
JSON line:

1. ``device`` — JAX's first device must be a TPU.  Otherwise the script
   says why and exits nonzero; it never falls back to the CPU.
2. ``cross_device`` — ``FederatedTrainer`` with ``client_batching="wave"``
   trains 64 clients of the Fig-8 CNN (28x28x1 FEMNIST-shaped data, 10
   classes) for 2 rounds of 5 local steps.  Round 2 must reuse round 1's
   compiled wave.  From the trained params, the same wave (same data,
   same keys) then runs on the chip and on the host CPU, and the
   per-client deltas are compared.
3. ``ragged_wave`` — 64 MLP clients with different batch sizes run as
   one wave through ``BatchedExecutor``'s accelerator default
   (``lax.ragged_dot``), compared with the CPU's masked-dense wave.
4. ``cross_silo`` — ``repro.launch.train`` at the published width of
   qwen1.5-0.5b: 2 silos, 2 rounds, 2 local steps, batch 8 x 128.  Every
   round's loss must be finite and the first step's within 1.0 of
   ln(vocab), the loss of a uniform guess.

Any failed check raises, so the script exits nonzero without printing
its last line, which is ``{"ok": true, "device": {...}}``.  The numbers
it prints are those of a smoke run, not of a benchmark.
"""
from __future__ import annotations

import contextlib
import copy
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

#: Deltas of a wave on the chip against the same wave on the host CPU, as
#: a share of each leaf's largest |delta|.  At the TPU's default precision
#: an f32 matmul or convolution multiplies bf16-rounded operands, and the
#: local steps' ReLU and max-pool switches flip on that rounding: on a v5e
#: the CNN wave's deltas then differ from f32 by up to 0.93 of a leaf's
#: largest entry, as they do on a CPU that rounds the conv operands to
#: bf16 (0.95).  So the compared waves run at precision "highest", where
#: the TPU computes f32 products in full.  The switches then flip only on
#: near-ties: rounding the conv operands to 21 mantissa bits on a CPU moves
#: the CNN's deltas by 8.6e-3 of a leaf's largest entry, the MLP's by
#: 6.8e-6.  2**-4 bounds that, while handing a client another client's
#: delta is off by 2.4 or more.
DELTA_TOL = 2.0 ** -4
CHECK_PRECISION = "highest"

#: per-client batch sizes of the ragged wave, cycled over the clients
RAGGED_BATCHES = (8, 16, 24, 32)


class SmokeFailure(RuntimeError):
    """A phase's output is not what the system should produce."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(record: Dict[str, Any]) -> None:
    print(json.dumps(record, default=str), flush=True)


# --------------------------------------------------------------------------
# wave versus reference
# --------------------------------------------------------------------------


def replay_wave(ex, params, clients: Sequence[Any], n_steps: int,
                round_idx: int, *, device=None,
                precision: Optional[str] = None) -> list:
    """One wave through ``ex`` on copies of ``clients``: every call replays
    the same batches and per-client keys.  ``device`` places the wave
    (default: JAX's default device); ``precision`` sets the default matmul
    precision while the wave is traced."""
    import jax

    clients = copy.deepcopy(list(clients))
    with contextlib.ExitStack() as stack:
        if device is not None:
            stack.enter_context(jax.default_device(device))
            params = jax.device_put(params, device)
        if precision is not None:
            stack.enter_context(jax.default_matmul_precision(precision))
        return ex.run_wave(params, clients, n_steps, round_idx)


def deltas_close(chip: list, ref: list, tol: float = DELTA_TOL) -> Tuple[bool, float]:
    """Per client and leaf, ``allclose`` with ``atol = tol * max|ref|``.
    Returns (all close, worst ``max|chip - ref| / max|ref|``)."""
    import jax

    check(len(chip) == len(ref), f"{len(chip)} chip results, {len(ref)} reference")
    ok, worst = True, 0.0
    for (dc, nc, _), (dr, nr, _) in zip(chip, ref):
        check(nc == nr, f"examples seen differ: {nc} vs {nr}")
        for a, b in zip(jax.tree.leaves(dc), jax.tree.leaves(dr)):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            scale = float(np.max(np.abs(b)))
            ok &= bool(np.allclose(a, b, rtol=0.0, atol=tol * scale))
            err = float(np.max(np.abs(a - b)))
            worst = max(worst, err / scale if scale else (math.inf if err else 0.0))
    return ok, worst


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def _femnist_clients(mcfg, n_clients: int, steps: int, seed: int):
    from repro.core.budget import fedscale_budget_distribution
    from repro.fed.trainer import build_fl_clients

    clients, test = build_fl_clients(
        mcfg, fedscale_budget_distribution(n_clients, seed=seed), "femnist",
        n_samples=64 * n_clients, batch_size=16, n_batches=steps, seed=seed,
    )
    for c in clients:  # FEMNIST's first 10 classes, as the examples use
        c.data.y = c.data.y % 10
    test["y"] = test["y"] % 10
    return clients, test


def cross_device_phase(ref_device, *, n_clients: int = 64, rounds: int = 2,
                       steps: int = 5, hidden: int = 64, seed: int = 0,
                       log: Callable = emit) -> Dict[str, Any]:
    """FedHC's trainer on batched client waves, then its wave program
    checked against the CPU."""
    import jax

    from repro.fed.batch_exec import BatchedExecutor
    from repro.fed.trainer import FedConfig, FederatedTrainer
    from repro.models.small import SmallModelConfig

    mcfg = SmallModelConfig(kind="cnn", n_classes=10, hidden=hidden,
                            n_layers=2, image_size=28, channels=1)
    clients, test = _femnist_clients(mcfg, n_clients, steps, seed)
    fed = FedConfig(rounds=rounds, participants_per_round=n_clients,
                    local_steps=steps, learning_rate=0.05,
                    client_batching="wave", seed=seed)
    tr = FederatedTrainer(mcfg, clients, fed, test_batch=test)
    ex = tr.batch_exec
    for _ in range(rounds):
        t0 = time.perf_counter()
        rec = tr.run_round()
        jax.block_until_ready(tr.params)
        log({"phase": "cross_device", "round": rec["round"],
             "wall_s": time.perf_counter() - t0,
             "completed": rec["completed"], "test_acc": rec["test_acc"],
             "wave": dict(ex.last_wave), "stats": ex.stats.as_dict()})
        check(ex.last_wave["mode"] == "dense"
              and ex.last_wave["clients"] == n_clients,
              f"round {rec['round']} did not run one dense wave of "
              f"{n_clients}: {ex.last_wave}")
    check(ex.stats.compiles == 1 and ex.stats.cache_hits == rounds - 1,
          f"rounds after the first must reuse the compiled wave: {ex.stats}")
    check(math.isfinite(rec["test_acc"]), "test accuracy is not finite")

    wave = (tr.params, tr.clients, steps, tr.round)
    ref_ex = BatchedExecutor(mcfg, tr.opt, fed.prox_mu, gmm_impl="dense")
    ref = replay_wave(ref_ex, *wave, device=ref_device)
    # the trainer's compiled wave, at the precision users get
    _, default_err = deltas_close(replay_wave(ex, *wave), ref)
    check(ex.last_wave["cache_hit"] is True,
          "the replayed wave did not reuse the trainer's compiled wave")
    chk_ex = BatchedExecutor(mcfg, tr.opt, fed.prox_mu)
    ok, worst = deltas_close(
        replay_wave(chk_ex, *wave, precision=CHECK_PRECISION), ref)
    out = {"phase": "cross_device.reference", "clients": n_clients,
           "platform": chk_ex.last_wave["platform"],
           "ref_platform": ref_ex.last_wave["platform"],
           "precision": CHECK_PRECISION, "max_rel_delta_err": worst,
           "tol": DELTA_TOL, "ok": ok,
           "default_precision_max_rel_delta_err": default_err}
    log(out)
    check(ok, f"cross-device deltas differ from the CPU reference by {worst}")
    return {**out, "test_acc": rec["test_acc"], "stats": ex.stats.as_dict()}


def ragged_wave_phase(ref_device, *, n_clients: int = 64, steps: int = 5,
                      hidden: int = 128, seed: int = 0,
                      log: Callable = emit) -> Dict[str, Any]:
    """One MLP wave whose clients have different batch sizes."""
    import jax

    from repro.fed.batch_exec import BatchedExecutor
    from repro.models.small import SmallModelConfig, init_small
    from repro.optim.optimizers import make_optimizer

    mcfg = SmallModelConfig(kind="mlp", n_classes=10, hidden=hidden,
                            n_layers=2, image_size=28, channels=1)
    clients, _ = _femnist_clients(mcfg, n_clients, steps, seed)
    for i, c in enumerate(clients):
        c.data.batch_size = RAGGED_BATCHES[i % len(RAGGED_BATCHES)]
    opt = make_optimizer("sgd", 0.05)
    params = init_small(jax.random.PRNGKey(seed), mcfg)
    wave = (params, clients, steps, 0)
    ref_ex = BatchedExecutor(mcfg, opt, gmm_impl="dense")
    ref = replay_wave(ref_ex, *wave, device=ref_device)
    ex = BatchedExecutor(mcfg, opt)  # the backend's default gmm_impl
    t0 = time.perf_counter()
    chip = replay_wave(ex, *wave)
    wall = time.perf_counter() - t0
    _, default_err = deltas_close(chip, ref)
    check(ex.last_wave["mode"] == "ragged", f"not a ragged wave: {ex.last_wave}")
    chk_ex = BatchedExecutor(mcfg, opt)
    ok, worst = deltas_close(
        replay_wave(chk_ex, *wave, precision=CHECK_PRECISION), ref)
    out = {"phase": "ragged_wave", "clients": n_clients,
           "rows_per_step": int(sum(c.data.batch_size for c in clients)),
           "gmm_impl": chk_ex.gmm_impl, "ref_gmm_impl": ref_ex.gmm_impl,
           "platform": chk_ex.last_wave["platform"],
           "ref_platform": ref_ex.last_wave["platform"],
           "first_wave_wall_s": wall, "precision": CHECK_PRECISION,
           "max_rel_delta_err": worst, "tol": DELTA_TOL, "ok": ok,
           "default_precision_max_rel_delta_err": default_err}
    log(out)
    check(ok, f"ragged-wave deltas differ from the CPU reference by {worst}")
    return out


def cross_silo_phase(*, arch: str = "qwen1.5-0.5b", reduced: bool = False,
                     silos: int = 2, rounds: int = 2, local_steps: int = 2,
                     batch: int = 8, seq: int = 128,
                     log: Callable = emit) -> Dict[str, Any]:
    """``repro.launch.train`` in this process, through its own ``main``."""
    from repro.configs.registry import get_config
    from repro.launch import train

    argv = ["--arch", arch, "--silos", str(silos), "--rounds", str(rounds),
            "--local-steps", str(local_steps), "--batch", str(batch),
            "--seq", str(seq)] + (["--reduced"] if reduced else [])
    t0 = time.perf_counter()
    res = train.main(argv)
    uniform = math.log(get_config(arch, reduced=reduced).vocab_size)
    out = {"phase": "cross_silo", "arch": res["arch"], "params": res["params"],
           "losses": res["losses"], "first_loss": res["first_loss"],
           "uniform_loss": uniform, "compile_s": res["compile_s"],
           "wall_s": time.perf_counter() - t0}
    log(out)
    check(len(res["losses"]) == rounds
          and all(math.isfinite(x) for x in res["losses"]),
          f"non-finite round losses: {res['losses']}")
    check(abs(res["first_loss"] - uniform) <= 1.0,
          f"first loss {res['first_loss']} is not within 1.0 of ln(vocab) "
          f"{uniform}")
    return out


# --------------------------------------------------------------------------


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no {SRC / 'repro'}; run this script from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # the references run on the host CPU in this process: keep its backend
    # next to the accelerator when the platforms are pinned
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform} "
              f"({dev.device_kind}), not a TPU; nothing was run",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    cpu = jax.devices("cpu")[0]
    emit({"phase": "device", "platform": dev.platform, "kind": dev.device_kind,
          "count": len(jax.devices()), "jax": jax.__version__,
          "compile_cache": cache})

    t0 = time.perf_counter()
    xd = cross_device_phase(cpu)
    check(xd["platform"] == "tpu", f"the trainer's wave ran on {xd['platform']}")
    check(xd["ref_platform"] == "cpu", f"the reference ran on {xd['ref_platform']}")
    rg = ragged_wave_phase(cpu)
    check(rg["platform"] == "tpu", f"the ragged wave ran on {rg['platform']}")
    check(rg["ref_platform"] == "cpu", f"the reference ran on {rg['ref_platform']}")
    cross_silo_phase()
    stats = dev.memory_stats() or {}
    emit({"phase": "memory", "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
          "bytes_limit": stats.get("bytes_limit"),
          "wall_s_total": time.perf_counter() - t0})

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
