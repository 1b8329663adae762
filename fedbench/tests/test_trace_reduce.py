"""The reduction from a profiler trace to busy time, program time and
idle time by phase: on hand-made planes, and on a small trace recorded on
a TPU v5e by ``record_trace.py`` (``data/small.xplane.pb``)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * 1e6, duration_ns=dur_ms * 1e6)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v) for k, v in lines.items()])


def test_hand_made_planes():
    host = plane("/host:CPU", python=[
        ev("fedbench.window", 10, 100),
        ev("round.collect", 10, 40), ev("round.aggregate", 50, 30)])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("fusion.1", 5, 10), ev("fusion.2", 12, 8), ev("dot.3", 60, 10),
                         ev("dot.3", 130, 5)],
                XLA_Modules=[ev("jit_wave(17)", 5, 20), ev("jit_add(2)", 60, 10)])
    s = tr.reduce_planes([host, dev])
    assert s.window_s == pytest.approx(0.100)
    # ops inside [10, 110] ms: [10, 20] and [60, 70]; the one at 130 is out
    assert s.busy_s == pytest.approx(0.020)
    assert s.idle_frac == pytest.approx(0.8)
    assert s.program_s == pytest.approx({"jit_wave": 0.015, "jit_add": 0.010})
    assert s.op_s["dot.3"] == pytest.approx(0.010)
    # idle: collect [20, 50] = 30 ms; aggregate [50, 60] + [70, 80] = 20 ms;
    # after the phases [80, 110] = 30 ms under no label
    assert s.idle_by_phase == pytest.approx(
        {"round.collect": 0.030, "round.aggregate": 0.020, "host.other": 0.030})
    assert s.top_idle(2)[0][0] in ("round.collect", "host.other")


def test_no_window_or_no_device_reads_nothing():
    dev = plane("/device:TPU:0", XLA_Ops=[ev("f", 0, 1)])
    assert tr.reduce_planes([plane("/host:CPU", python=[]), dev]) is None
    assert tr.reduce_planes([plane("/host:CPU", python=[ev("fedbench.window", 0, 5)])]) is None


def test_merge_and_gaps():
    assert tr.merge([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.gaps([(0, 2.5), (3, 4)], -1, 5) == [(-1, 0), (2.5, 3), (4, 5)]


def test_recorded_v5e_trace():
    """Three calls of a jitted program inside ``round.collect``, each
    followed by idle stretches under ``round.collect``, ``round.aggregate``
    and no label (see ``record_trace.py``)."""
    s = tr.reduce_file(DATA)
    assert s is not None and s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert set(s.idle_by_phase) == {"round.collect", "round.aggregate", "host.other"}
    # the host sleeps 3 ms under round.aggregate each of 3 times
    assert s.idle_by_phase["round.aggregate"] == pytest.approx(0.009, rel=0.5)
    assert any("wave_probe" in k for k in s.program_s)
