"""The program's ``fedhc.*`` spans read from a profiler trace: on
hand-made planes with nested spans and args, on the small trace recorded
on a TPU v5e (``data/small.xplane.pb``, from a program without such
spans), and through the result line of a traced ``run.py``."""
import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import replay
import run
import span_reduce as sr
import trace_reduce as tr
from test_harness import checkout, small_mix  # noqa: F401 (a fixture)

FEDBENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
#: the per-layer metrics of ``BENCHMARK.json`` read from the program's spans
SPAN_METRICS = sorted(
    m["name"] for m in json.loads((FEDBENCH.parent / "BENCHMARK.json").read_text())["per_layer"]
    if m["source"] == "program_span")


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * 1e6, duration_ns=dur_ms * 1e6,
              stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v) for k, v in lines.items()])


def hand_made():
    """One round's collect and aggregate in a 100 ms window ([10, 110] ms),
    with the program's spans nested inside the harness's phases."""
    host = plane("/host:CPU", python=[
        ev("fedhc.phase.sample", 0, 10, round=0),         # ends as the window opens
        ev("fedbench.window", 10, 100),
        ev("round.collect", 10, 50),
        ev("fedhc.phase.collect", 10, 50, round=0),
        ev("fedhc.client.batch_wave", 12, 46, round=0, clients=8, mode="ragged"),
        ev("fedhc.wave.prepare", 12, 18, clients=8, mode="ragged"),
        ev("fedhc.wave.launch", 30, 5, rows=40, h2d_bytes=1000),
        ev("fedhc.wave.wait", 35, 15),
        ev("fedhc.wave.fetch", 50, 6, d2h_bytes=5000),
        ev("round.aggregate", 60, 30),
        ev("fedhc.phase.aggregate", 60, 30, round=0),
        ev("fedhc.fold.sum", 62, 18, deltas=8, bytes=4000),
        ev("fedhc.fold.apply", 80, 5),
        ev("fedhc.phase.report", 100, 20, round=0),       # cut by the window's end
    ])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("while.1", 33, 15), ev("add.2", 81, 3)],
                XLA_Modules=[ev("jit_wave(3)", 33, 15)])
    return [host, dev]


def base_fields(summary):
    return {f.name: getattr(summary, f.name) for f in dataclasses.fields(tr.TraceSummary)}


def test_spans_args_and_idle_by_span_on_hand_made_planes():
    planes = hand_made()
    s = sr.reduce_planes(planes)
    assert base_fields(s) == base_fields(tr.reduce_planes(planes))
    assert s.span_s == pytest.approx({
        "fedhc.phase.collect": 0.050, "fedhc.client.batch_wave": 0.046,
        "fedhc.wave.prepare": 0.018, "fedhc.wave.launch": 0.005, "fedhc.wave.wait": 0.015,
        "fedhc.wave.fetch": 0.006, "fedhc.phase.aggregate": 0.030, "fedhc.fold.sum": 0.018,
        "fedhc.fold.apply": 0.005, "fedhc.phase.report": 0.010})
    assert set(s.span_n.values()) == {1} and "fedhc.phase.sample" not in s.span_n
    assert s.span_args["fedhc.wave.fetch"] == {"d2h_bytes": 5000}
    assert s.span_args["fedhc.wave.launch"] == {"rows": 40, "h2d_bytes": 1000}
    # strings are not summed
    assert s.span_args["fedhc.wave.prepare"] == {"clients": 8}
    assert s.span_args["fedhc.fold.sum"] == {"deltas": 8, "bytes": 4000}
    # idle by the innermost span: the wave ran [33, 48], the apply [81, 84]
    assert s.idle_by_span == pytest.approx({
        "fedhc.phase.collect": 0.004, "fedhc.wave.prepare": 0.018, "fedhc.wave.launch": 0.003,
        "fedhc.wave.wait": 0.002, "fedhc.wave.fetch": 0.006, "fedhc.client.batch_wave": 0.002,
        "fedhc.phase.aggregate": 0.007, "fedhc.fold.sum": 0.018, "fedhc.fold.apply": 0.002,
        "host.other": 0.010, "fedhc.phase.report": 0.010})
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s - s.busy_s)


def test_innermost_flattens_nested_spans():
    spans = [(0, 10, "p"), (2, 5, "c"), (6, 8, "d"), (3, 4, "g"), (12, 13, "q")]
    assert sr.innermost(spans) == [(0, 2, "p"), (2, 3, "c"), (3, 4, "g"), (4, 5, "c"),
                                   (5, 6, "p"), (6, 8, "d"), (8, 10, "p"), (12, 13, "q")]
    assert sr.innermost([]) == []


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metrics_read(name):
    """Each span metric reads its span per round, and nothing from a
    trace without the program's spans or from no trace."""
    mod = replay.load_module(FEDBENCH / "metrics" / f"{name}.py", f"metric_{name}")
    s = sr.reduce_planes(hand_made())
    expect = {"wave_prepare_ms": 9.0, "wave_launch_ms": 2.5, "wave_wait_ms": 7.5,
              "wave_fetch_ms": 3.0, "fold_host_ms": 9.0, "d2h_mb": 0.0025}[name]
    assert mod.read({"trace": s, "rounds": 2}) == pytest.approx(expect)
    assert mod.read({"trace": tr.reduce_file(DATA), "rounds": 2}) is None
    assert mod.read({"trace": sr.reduce_file(DATA), "rounds": 2}) is None
    assert mod.read({"trace": None, "rounds": 2}) is None


def test_recorded_v5e_trace_reads_as_trace_reduce():
    s = sr.reduce_file(DATA)
    assert base_fields(s) == base_fields(tr.reduce_file(DATA))
    assert s.span_s == {} and s.span_args == {}
    assert s.idle_by_span == pytest.approx({"host.other": s.window_s - s.busy_s})


def test_traced_run_adds_the_span_metrics(checkout, monkeypatch):
    """A traced ``run.py`` prints the span metrics of ``BENCHMARK.json``,
    and the spans and the idle split under ``run`` (the trace replaced by
    the hand-made planes: a CPU trace has no device plane)."""
    root, add_cell, _ = checkout
    name = add_cell("fedavg_2nn.ragged_b10_50", "fedavg_2nn",
                    small_mix("ragged_b10_50", [[4, 0.5], [8, 0.5]]),
                    {k: {"limit": 1e-3} for k in replay.NUMBERS})
    # the span metrics list the cells they are read in: the copy's too
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in SPAN_METRICS and "workloads" in m:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(sr, "reduce_file", lambda path: sr.reduce_planes(hand_made()))
    lines = []
    rc = run.main(["--workload", name, "--seed", "987654321012", "--seconds", "1",
                   "--trace", "1"], root=root, require_tpu=False, emit=lines.append)
    assert rc == 0
    out = json.loads(lines[-1])
    assert len(SPAN_METRICS) == 6 and set(SPAN_METRICS) <= set(out["metrics"])
    assert out["metrics"]["d2h_mb"]["unit"] == "MB"
    assert sum(out["run"]["idle_by_span"].values()) == pytest.approx(0.082)
    assert out["run"]["span_args"]["fedhc.wave.fetch"] == {"d2h_bytes": 5000}
    assert out["run"]["span_n"]["fedhc.wave.wait"] == 1
    assert out["run"]["span_s"]["fedhc.fold.sum"] == pytest.approx(0.018)


def with_a_device(path):
    """A real CPU trace, read as ``span_reduce`` reads a chip's, with one
    device plane added: the host's spans are the run's own."""
    from jax.profiler import ProfileData

    planes = [plane(p.name, **{line.name.replace(" ", "_"): [
        NS(name=e.name, start_ns=e.start_ns, duration_ns=e.duration_ns,
           stats=list(e.stats) if e.name.startswith(sr.PREFIX) else [])
        for e in line.events] for line in p.lines}) for p in ProfileData.from_file(str(path)).planes]
    lo = min(e.start_ns for p in planes for line in p.lines for e in line.events
             if e.name == tr.WINDOW)
    dev = plane("/device:TPU:0", XLA_Ops=[NS(name="add.1", start_ns=lo, duration_ns=1e6)],
                XLA_Modules=[NS(name="jit_add(1)", start_ns=lo, duration_ns=1e6)])
    return sr.reduce_planes(planes + [dev])


def test_a_cell_without_waves_reads_the_fold_span(checkout, monkeypatch):
    """The fold's span opens in every FedAvg round, so ``fold_host_ms``
    lists no cells and a new cell reads it by its own files; a cell that
    trains one client at a time has no wave spans and is given none of
    the wave metrics.  The program's spans are those of a real traced run
    on the CPU, with a device plane added (a CPU trace has none)."""
    from test_harness import add_seqcls, run_cell

    root, add_cell, unchanged = checkout
    name = add_seqcls(root, add_cell)
    listed = {m["name"] for m in run.load_cell(name, root)["per_layer"]}
    assert listed & set(SPAN_METRICS) == {"fold_host_ms"}
    monkeypatch.setattr(sr, "reduce_file", with_a_device)
    out = run_cell(root, name, trace=1)
    assert unchanged()
    assert out["metrics"]["fold_host_ms"]["value"] > 0
    assert not set(out["metrics"]) & (set(SPAN_METRICS) - {"fold_host_ms"})
    assert out["run"]["span_n"]["fedhc.fold.sum"] == out["run"]["rounds"]
    assert out["run"]["span_n"]["fedhc.client.train"] > 0
    assert not any(k.startswith("fedhc.wave.") for k in out["run"]["span_n"])
