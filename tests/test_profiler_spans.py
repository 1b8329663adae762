"""The program's spans on the JAX profiler's clock (``repro.obs.trace.span``):
one batched trainer round profiled on the CPU, dense and ragged, with
every ``fedhc.*`` span of the wave, the fold and the phases present,
nested and carrying its args; the wave's transfer counters exact from
shapes; the round's parameters unchanged by profiling; and the same
spans in a ``Tracer`` under their own names."""
import hashlib
import os
import subprocess
import sys
from collections import Counter as Multiset

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.budget import WorkloadSpec
from repro.core.runtime import FixedRuntime
from repro.data.pipeline import ClientDataset
from repro.fed.client import FLClient
from repro.fed.trainer import FedConfig, FederatedTrainer
from repro.models.small import SmallModelConfig
from repro.obs import ObsPlane, Tracer, span

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

#: a 2NN-shaped MLP, small
MCFG = SmallModelConfig(kind="mlp", n_classes=10, hidden=16, n_layers=2, image_size=8,
                        channels=1)
CLIENTS, STEPS = 4, 2
BATCHES = {"dense": [8, 8, 8, 8], "ragged": [4, 8, 4, 8]}
WAVE = ("fedhc.wave.prepare", "fedhc.wave.launch", "fedhc.wave.wait", "fedhc.wave.fetch")
FOLD = ("fedhc.fold.sum", "fedhc.fold.apply")
PHASES = tuple(f"fedhc.phase.{p}" for p in
               ("sample", "simulate", "dispatch", "collect", "aggregate", "report"))


def _trainer(mode, obs=None):
    rng = np.random.default_rng(3)
    clients = []
    for i, bs in enumerate(BATCHES[mode]):
        x = rng.normal(size=(24, 8, 8, 1)).astype(np.float32)
        y = rng.integers(0, 10, size=24).astype(np.int32)
        clients.append(FLClient(i, 100.0, ClientDataset(x, y, bs, seed=i), WorkloadSpec()))
    fed = FedConfig(rounds=1, participants_per_round=CLIENTS, local_steps=STEPS,
                    learning_rate=0.1, client_batching="wave", seed=5)
    return FederatedTrainer(MCFG, clients, fed, runtime=FixedRuntime(2.0, 1.0), obs=obs)


def _digest(params):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _profiled_round(tmp_path, tr):
    jax.profiler.start_trace(str(tmp_path))
    try:
        rec = tr.run_round()
        jax.block_until_ready(tr.params)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats) if ev.name.startswith("fedhc.") else {}
                events.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, stats))
    return rec, events


def _one(events, name):
    found = [e for e in events if e[0] == name]
    assert len(found) == 1, (name, [e[0] for e in events if e[0].startswith("fedhc.")])
    return found[0]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _wave_bytes(mode, tr, rec):
    """(h2d, d2h) of the round's wave, from shapes: the wave's host inputs;
    every client's float32 metrics (the deltas stay on the device); and
    the parameters' bytes, one client's delta."""
    sizes = BATCHES[mode]
    rows = sum(sizes)
    x_row, f32, i32 = 8 * 8 * 1 * 4, 4, 4
    keys = CLIENTS * 2 * 4                                     # uint32 (hi, lo)
    h2d = STEPS * rows * (x_row + i32) + keys
    if mode == "ragged":
        h2d += CLIENTS * i32 + rows * i32                      # group sizes, segment ids
    param_bytes = sum(a.nbytes for a in jax.tree.leaves(tr.params))
    n_metrics = sum(1 for k in rec if k.startswith("train_"))
    return h2d, CLIENTS * n_metrics * f32, param_bytes


@pytest.mark.parametrize("mode", ["dense", "ragged"])
def test_profiled_round_has_every_span_nested_with_args(tmp_path, mode):
    tr = _trainer(mode)
    rec, events = _profiled_round(tmp_path, tr)
    assert tr.batch_exec.last_wave["mode"] == mode
    names = [e[0] for e in events]
    # the program's spans are all prefixed: none reads as a harness phase
    assert not [n for n in names if n.startswith("round.")]
    phases = {n: _one(events, n) for n in PHASES}
    for n, e in phases.items():
        assert e[3]["round"] == 0, n
    batch = _one(events, "fedhc.client.batch_wave")
    wave = [_one(events, n) for n in WAVE]
    assert _inside(batch, phases["fedhc.phase.collect"])
    for a, b in zip(wave, wave[1:]):
        assert _inside(a, batch) and a[2] <= b[1]             # in order, apart
    assert _inside(wave[-1], batch)
    agg = _one(events, "fedhc.round.aggregate")
    fold = [_one(events, n) for n in FOLD]
    assert _inside(agg, phases["fedhc.phase.aggregate"])
    assert all(_inside(f, agg) for f in fold) and fold[0][2] <= fold[1][1]

    h2d, d2h, param_bytes = _wave_bytes(mode, tr, rec)
    prepare, launch, _, fetch = wave
    assert prepare[3] == {"clients": CLIENTS, "mode": mode}
    assert launch[3] == {"rows": sum(BATCHES[mode]), "h2d_bytes": h2d}
    assert fetch[3] == {"d2h_bytes": d2h}
    assert batch[3]["clients"] == CLIENTS and batch[3]["mode"] == mode
    assert fold[0][3] == {"deltas": CLIENTS, "bytes": CLIENTS * param_bytes, "h2d_bytes": 0}
    assert agg[3] == {"round": 0, "deltas": CLIENTS}


@pytest.mark.parametrize("mode", ["dense", "ragged"])
def test_profiling_leaves_the_round_bit_identical(tmp_path, mode):
    plain = _trainer(mode)
    plain.run_round()
    traced = _trainer(mode)
    _profiled_round(tmp_path, traced)
    assert _digest(traced.params) == _digest(plain.params)
    assert traced.history == plain.history


@pytest.mark.parametrize("mode", ["dense", "ragged"])
def test_tracer_holds_the_same_spans_and_counters_match(tmp_path, mode):
    obs = ObsPlane()
    tr = _trainer(mode, obs)
    _, events = _profiled_round(tmp_path, tr)
    prof = [(e[0][len("fedhc."):], e[3]) for e in events if e[0].startswith("fedhc.")]
    walls = [(ev[1], ev[9] or {}) for ev in obs.tracer.events if ev[0] == "X" and ev[7] is not None]
    assert Multiset(n for n, _ in walls) == Multiset(n for n, _ in prof)
    for name, args in walls:
        kept = {k: v for k, v in args.items()
                if isinstance(v, (int, str)) and not isinstance(v, bool)}
        assert any({k: a.get(k) for k in kept} == kept for n, a in prof if n == name), name
    reg = obs.registry
    launch = [a for n, a in prof if n == "wave.launch"]
    fetch = [a for n, a in prof if n == "wave.fetch"]
    assert reg.counter("client.h2d_bytes", tr.tenant).value == sum(a["h2d_bytes"] for a in launch)
    assert reg.counter("client.d2h_bytes", tr.tenant).value == sum(a["d2h_bytes"] for a in fetch)
    fold_sum = [a for n, a in prof if n == "fold.sum"]
    assert reg.counter("fold.h2d_bytes", tr.tenant).value == sum(a["h2d_bytes"] for a in fold_sum)
    # a wave has no per-client training time
    assert reg.histogram("client.train_seconds", tr.tenant).snapshot()["count"] == 0


def test_single_client_wave_moves_nothing_through_the_wave_path():
    """A one-client wave runs the sequential step: a ``wave.prepare``
    span, no launch or fetch, and the transfer counters stay at zero."""
    obs = ObsPlane()
    tr = _trainer("dense", obs)
    tr.batch_exec.run_wave(tr.params, tr.clients[:1], STEPS)
    assert [ev[1] for ev in obs.tracer.events] == ["wave.prepare"]
    assert obs.tracer.events[0][9] == {"clients": 1, "mode": "seq"}
    assert obs.registry.counter("client.h2d_bytes", tr.tenant).value == 0
    assert obs.registry.counter("client.d2h_bytes", tr.tenant).value == 0


def test_span_records_late_args_and_duration():
    t = Tracer()
    with span("unit.work", t, "p", "lane", a=1) as sp:
        sp.set(b="x")
    assert sp.seconds >= 0
    (ev,) = t.events
    assert ev[:5] == ("X", "unit.work", "wall", "p", "lane")
    assert ev[8] == pytest.approx(sp.seconds) and ev[9] == {"a": 1, "b": "x"}
    with span("unit.quiet") as sp:                              # no tracer: nothing kept
        pass
    assert len(t.events) == 1


def test_span_records_nothing_when_the_body_raises():
    t = Tracer()
    with pytest.raises(ValueError):
        with span("unit.fails", t, "p", "lane"):
            raise ValueError("boom")
    assert t.events == []


def test_obs_imports_and_spans_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "import repro.obs as o\n"
            "t = o.Tracer()\n"
            "with o.span('x.y', t, 'p', 't', n=1): pass\n"
            "assert [e[1] for e in t.events] == ['x.y']\n"
            "assert 'jax.profiler' not in sys.modules\n")
    env = {**os.environ, "PYTHONPATH": SRC}
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
