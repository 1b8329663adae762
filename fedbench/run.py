#!/usr/bin/env python3
"""One run of one benchmark cell on the chip this process finds.

    python3 fedbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a model configuration
(``configs/<name>.json``, found through the cell's config entry) and a
traffic mix (``traffic/<mix>.json``).  The run:

1. refuses to run (exit 2, no result) unless JAX's first device is a TPU
   whose ``device_kind`` is in ``peaks.json`` and there are as many chips
   as the cell asks for;
2. set-up: makes the client pool and its data (``generate.py``) and the
   initial weights (``reference/<kind>.py``) from the seed, builds
   ``FederatedTrainer`` with the mix's ``FedConfig`` knobs, and drives it
   through the mix's checked rounds with the window's own calls while
   ``replay.Recorder`` keeps what the check needs; where the executor ran
   a ragged wave, every per-round composition of batch sizes the pool can
   plausibly draw is warmed too (the program compiles one ragged program
   per total row count);
3. the window: whole rounds through ``begin_round``/``step_round``, each
   phase timed on the host clock (and, with ``--trace 1``, wrapped in a
   ``round.<phase>`` profiler annotation), until ``--seconds`` have passed
   at the end of a round;
4. reads the device's peak memory; then, outside the timing, puts the
   benchmark's initial weights back and drives one round at each end of
   the range of batch-size compositions (``generate.check_participants``),
   so that the compiled programs at the window's extreme sizes are checked
   too;
5. frees the program, replays the set-up's rounds and those after the
   window in the plain reference and compares (``replay.py``), and prints
   each compared number beside its limit on standard error, then one JSON
   line on standard output.

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics; each is read by
``metrics/<name>.py`` from the window's timers, counters and trace.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

#: the event JAX records each time it builds an executable, whether it
#: compiles it or loads it from the persistent cache
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(RuntimeError):
    """The run cannot be measured here; it prints no result."""


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path, need_limits: bool = True) -> Dict[str, Any]:
    """Everything one cell is made of, found by name.  A cell is refused
    unless its limits give every number a limit or the reason it is not
    compared (``need_limits``)."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    limits_path = root / "fedbench" / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}
    from replay import NUMBERS

    missing = [k for k in NUMBERS if (limits.get(k) or {}).get("limit") is None
               and "not_compared" not in (limits.get(k) or {})]
    if missing and need_limits:
        raise Refused(f"no limit for {', '.join(missing)} in {limits_path.name}")
    return {
        "cell": cell,
        "config": load_json(root / entry["file"]),
        "traffic": load_json(root / "fedbench" / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if _in_cell(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _in_cell(m, name)],
        "limits": limits,
        "root": root,
    }


def check_device(chips: int, peaks: Dict[str, Any]):
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise Refused(f"JAX's first device is {dev.platform} ({dev.device_kind}), not a TPU")
    if dev.device_kind not in peaks:
        raise Refused(f"device kind {dev.device_kind!r} is not in peaks.json")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return dev


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------


def build_trainer(spec: Dict[str, Any], seed: int):
    """The pool, the trainer and the initial weights, all from ``seed``."""
    import jax

    from generate import make_pool
    from replay import load_reference
    from repro.core.budget import WorkloadSpec
    from repro.data.pipeline import ClientDataset
    from repro.fed.client import FLClient
    from repro.fed.trainer import FedConfig, FederatedTrainer
    from repro.models.small import SmallModelConfig

    config, traffic = spec["config"], spec["traffic"]
    m = config["model"]
    steps = int(traffic["local_steps"])
    pool = make_pool(seed, traffic, config)
    clients = [
        FLClient(cid, float(pool.budgets[cid]),
                 ClientDataset(pool.x[cid], pool.y[cid], int(pool.batch_sizes[cid]),
                               seed=int(pool.data_seeds[cid])),
                 WorkloadSpec(model=m["kind"], n_layers=m["n_layers"],
                              batch_size=int(pool.batch_sizes[cid]), n_batches=steps))
        for cid in range(len(pool.x))]
    fed = FedConfig(rounds=1 << 30, participants_per_round=int(traffic["participants_per_round"]),
                    local_steps=steps, seed=pool.fed_seed, **traffic["fed"])
    tr = FederatedTrainer(SmallModelConfig(**m), clients, fed, test_batch=pool.test)
    ref = load_reference(m["kind"], spec["root"] / "fedbench")
    theta0 = jax.jit(lambda k: ref.init(k, m))(jax.random.key(pool.weight_key))
    own = tr.params["main"]
    if (jax.tree.structure(own) != jax.tree.structure(theta0)
            or [a.shape for a in jax.tree.leaves(own)]
            != [a.shape for a in jax.tree.leaves(theta0)]):
        raise RuntimeError("the program's parameter layout differs from the reference's")
    tr.params = {"main": theta0}
    return tr, pool, ref


def warm_compositions(tr, traffic: Dict[str, Any], config: Dict[str, Any]) -> int:
    """Run one wave through the trainer's own executor for every per-round
    composition of batch sizes the pool can plausibly draw, so that no
    program is built inside the window.  Returns the waves run."""
    import numpy as np

    from generate import composition_range
    from repro.data.pipeline import ClientDataset
    from repro.fed.client import FLClient

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


def checked_rounds(tr, recorder, n: int) -> None:
    """``n`` rounds through the window's own calls, watched by ``recorder``."""
    from repro.fed.trainer import RoundPhase

    for _ in range(n):
        st = tr.begin_round()
        while st.phase is not RoundPhase.DONE:
            before = st.phase
            tr.step_round(st)
            if before is RoundPhase.DISPATCH:
                recorder.after_dispatch(st)
            elif before is RoundPhase.COLLECT and st.phase is RoundPhase.AGGREGATE:
                recorder.after_collect(st)
            elif before is RoundPhase.REPORT:
                recorder.after_report(tr, st)


def rounds_after_window(tr, pool, traffic: Dict[str, Any], seed: int, theta0_host):
    """The rounds checked after the window: from the benchmark's initial
    weights, one round for each participant set of
    ``generate.check_participants``, through the window's own calls.  The
    trainer's draw of participants is replaced for these rounds only."""
    import jax

    import replay
    from generate import check_participants

    groups = check_participants(seed, traffic, pool.batch_sizes)
    n = len(groups)
    rec = replay.Recorder(theta0_host, n, norms_rounds=range(n), delta_rounds=range(n))
    tr.params = {"main": jax.device_put(theta0_host)}
    try:
        for ids in groups:
            chosen = [tr.clients[int(i)] for i in ids]
            tr._sample = lambda chosen=chosen: list(chosen)
            checked_rounds(tr, rec, 1)
    finally:
        tr.__dict__.pop("_sample", None)
    return rec


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------


class BuildCounter:
    """Counts executables JAX builds while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event == BUILD_EVENT:
            self.count += 1


def window(tr, seconds: float, trace: bool, counts, builds: BuildCounter) -> Dict[str, Any]:
    """Whole rounds until ``seconds`` have passed at a round's end."""
    import jax

    from repro.fed.trainer import RoundPhase

    annotate: Callable[[str], Any] = (
        jax.profiler.TraceAnnotation if trace else (lambda _: contextlib.nullcontext()))
    phase_s: Dict[str, float] = {}
    round_s: List[float] = []
    attempted = completed = 0
    clock = time.perf_counter
    builds.count, builds.on = 0, True
    with annotate("fedbench.window"):
        t0 = clock()
        while True:
            r0 = clock()
            st = tr.begin_round()
            while st.phase is not RoundPhase.DONE:
                ph = st.phase
                a = clock()
                with annotate(f"round.{ph.value}"):
                    tr.step_round(st)
                    if trace and ph is RoundPhase.AGGREGATE:
                        jax.block_until_ready(tr.params)
                phase_s[ph.value] = phase_s.get(ph.value, 0.0) + clock() - a
            r1 = clock()
            round_s.append(r1 - r0)
            attempted += len(st.participants)
            completed += int(st.rec["completed"])
            for cid, _ in st.finishers:
                counts.add(tr.fed.local_steps, st.by_id[cid].data.batch_size)
            if r1 - t0 >= seconds:
                break
    builds.on = False
    return {"window_s": r1 - t0, "round_s": round_s, "phase_s": phase_s,
            "rounds": len(round_s), "attempted": attempted, "completed": completed,
            "compiles_in_window": builds.count}


# --------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None, *, root: Path = ROOT, require_tpu: bool = True,
         t_start: Optional[float] = None, emit: Callable[[str], None] = print) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    if not (root / "src" / "repro").is_dir():
        print(f"fedbench: no program under {root / 'src'}; run from a checkout",
              file=sys.stderr)
        return 2
    for path in (str(root / "src"), str(HERE)):
        if path not in sys.path:
            sys.path.insert(0, path)
    try:
        spec = load_cell(args.workload, root)
        peaks = load_json(root / "fedbench" / "peaks.json")
        import jax

        if require_tpu:
            dev = check_device(int(spec["cell"]["chips"]), peaks)
        else:
            dev = jax.devices()[0]
    except Refused as e:
        print(f"fedbench: {e}; nothing was run", file=sys.stderr)
        return 2

    import flops
    import replay
    import trace_reduce
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program goes to the cache, so a run after the first builds none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    config, traffic = spec["config"], spec["traffic"]
    precision = config.get("matmul_precision", "default")

    def prec_ctx():
        return (contextlib.nullcontext() if precision == "default"
                else jax.default_matmul_precision(precision))
    builds = BuildCounter()
    with prec_ctx():
        marks = {"start": time.perf_counter() - t_start}
        tr, pool, ref = build_trainer(spec, args.seed)
        theta0_host = jax.device_get(tr.params["main"])
        marks["built"] = time.perf_counter() - t_start
        recorder = replay.Recorder(theta0_host, int(traffic["checked_rounds"]))
        checked_rounds(tr, recorder, recorder.n_rounds)
        marks["checked_rounds"] = time.perf_counter() - t_start
        warmed = 0
        if tr.batch_exec is not None and tr.batch_exec.last_wave.get("mode") == "ragged":
            warmed = warm_compositions(tr, traffic, config)
        jax.block_until_ready(tr.params)
        # the set-up's garbage is collected here, not in the window's first rounds
        gc.collect()
        setup_s = time.perf_counter() - t_start
        marks["warmed"] = setup_s

        counts = flops.StepCounts(ref, config["model"])
        log_dir = None
        if args.trace:
            log_dir = tempfile.mkdtemp(prefix="fedbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        w = window(tr, args.seconds, bool(args.trace), counts, builds)
        if args.trace:
            jax.profiler.stop_trace()

    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    t_after = time.perf_counter()
    with prec_ctx():
        after = rounds_after_window(tr, pool, traffic, args.seed, theta0_host)
    marks["after_window_s"] = time.perf_counter() - t_after
    summary = None
    if log_dir is not None:
        xp = trace_reduce.find_xplane(log_dir)
        summary = trace_reduce.reduce_file(xp) if xp else None
        shutil.rmtree(log_dir, ignore_errors=True)

    # the program's state goes before the reference runs
    steps, lr = tr.fed.local_steps, tr.fed.learning_rate
    del tr
    gc.collect()
    t_ref = time.perf_counter()
    ref_setup = replay.follow(ref, config["model"], theta0_host, recorder.rounds, steps, lr,
                              pool.test)
    ref_after = replay.follow(ref, config["model"], theta0_host, after.rounds, steps, lr,
                              pool.test, norms_rounds=range(after.n_rounds),
                              against=[after.program])
    numbers = replay.compare([recorder.program, after.program], [ref_setup, ref_after])
    checks = replay.judge(numbers, spec["limits"])
    correct = replay.passed(checks)
    marks["reference_s"] = time.perf_counter() - t_ref

    ctx = {**w, "setup_s": setup_s, "trace": summary, "peak": peaks.get(dev.device_kind),
           "memory_peak_bytes": memory_peak, "counts": counts}
    metrics = {}
    for mdef in spec["per_layer" if args.trace else "end_to_end"]:
        mod = replay.load_module(root / "fedbench" / "metrics" / f"{mdef['name']}.py",
                                 f"fedbench_metric_{mdef['name']}")
        v = mod.read(ctx)
        if v is not None:
            metrics[mdef["name"]] = {"value": float(v), "unit": mdef["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": w["attempted"],
        "failed": w["attempted"] - w["completed"], "metrics": metrics, "device": device,
        "run": {"rounds": w["rounds"], "window_s": w["window_s"], "setup_s": setup_s,
                "warmed_compositions": warmed, "marks": marks, "readings": numbers,
                "compiles_in_window": w["compiles_in_window"], "phase_s": w["phase_s"],
                "round_s": w["round_s"]},
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(), "idle_gaps": summary.top_idle()}
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    emit(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
