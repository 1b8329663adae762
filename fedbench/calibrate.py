#!/usr/bin/env python3
"""Readings from which a cell's limits are set (``limits/<cell>.json``).

    python3 fedbench/calibrate.py --workload <cell> --seeds 1,2,3 [--control] [--faults]

For each seed, in one process: the run's set-up (pool, trainer, the
checked rounds through the window's own calls), no window, the rounds
checked after the window, then the plain reference at float32 "highest"
and the five gaps of the program from it.  ``--control`` adds the
reference computed in bfloat16 throughout, put in the program's place;
``--faults`` adds the reference with half of each batch left out (the mean
taken over the rest), put in the program's place, and the program's own
readings with one client's delta after the window turned to the opposite
sign (read by ``client_dir_gap``).  A state left unchanged reads 1 on the
gaps of norms by construction and is not run.  One JSON line per seed and
side.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", action="store_true")
    p.add_argument("--faults", action="store_true")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]

    import jax
    import numpy as np

    import replay
    import run
    from repro.launch.compile_cache import enable_compile_cache

    spec = run.load_cell(args.workload, ROOT, need_limits=False)
    run.check_device(int(spec["cell"]["chips"]), run.load_json(HERE / "peaks.json"))
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    m, traffic = spec["config"]["model"], spec["traffic"]
    n_rounds = int(traffic["checked_rounds"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        tr, pool, ref = run.build_trainer(spec, seed)
        theta0 = jax.device_get(tr.params["main"])
        rec = replay.Recorder(theta0, n_rounds)
        run.checked_rounds(tr, rec, n_rounds)
        after = run.rounds_after_window(tr, pool, traffic, seed, theta0)
        steps, lr = tr.fed.local_steps, tr.fed.learning_rate
        t_prog = time.perf_counter() - t0
        del tr
        gc.collect()

        def follow(rounds, n=1, **kw):
            return replay.follow(ref, m, theta0, rounds, steps, lr, pool.test,
                                 norms_rounds=range(n), **kw)

        n_after = after.n_rounds
        sides = [("program", rec.program, after.program)]
        if args.control:
            sides.append(("control_bf16",
                          follow(rec.rounds, dtype="bfloat16", precision=None),
                          follow(after.rounds, n_after, dtype="bfloat16", precision=None,
                                 delta_rounds=range(n_after))))
        if args.faults:
            sides.append(("fault_half_batch", follow(rec.rounds, fault="half_batch"),
                          follow(after.rounds, n_after, fault="half_batch",
                                 delta_rounds=range(n_after))))
            flipped = copy.copy(after.program)
            flipped.client_cos = {}
            flipped.client_deltas = {r: list(ds) for r, ds in after.program.client_deltas.items()}
            for ds in flipped.client_deltas.values():
                ds[len(ds) // 2] = jax.tree.map(np.negative, ds[len(ds) // 2])
            sides.append(("fault_one_sign", rec.program, flipped))
        t1 = time.perf_counter()
        ref_setup = follow(rec.rounds)
        ref_after = follow(after.rounds, n_after, against=[a for _, _, a in sides])
        t_ref = time.perf_counter() - t1
        # the program's three worst clients by their delta norms, with the
        # samples they hold and their batch size
        worst = sorted(
            (replay.worst_leaf_gap(pn, rn), stretch, int(cid), len(pool.x[cid]),
             int(pool.batch_sizes[cid]))
            for stretch, prog, rf, rounds in (("setup", rec.program, ref_setup, rec.rounds),
                                              ("after", after.program, ref_after, after.rounds))
            for r, norms in prog.client_norms.items()
            for cid, pn, rn in zip(rounds[r].cids, norms, rf.client_norms[r]))[-3:]
        for side, setup, aft in sides:
            print(json.dumps({"workload": args.workload, "seed": seed, "side": side,
                              **replay.compare([setup, aft], [ref_setup, ref_after]),
                              "setup_and_rounds_s": t_prog, "reference_s": t_ref,
                              **({"worst_clients": worst} if side == "program" else {})}),
                  flush=True)
        del rec, after, ref_setup, ref_after, sides
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
