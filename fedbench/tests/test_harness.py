"""The harness end to end on the CPU at a small size: a cell and a family
added from new files alone, the refusals, and the faults and the control
that the check must catch."""
import dataclasses
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

import generate
import replay
import run

FEDBENCH = Path(__file__).resolve().parents[1]
ROOT = FEDBENCH.parent
#: the sources of a family for the tests only (sentence classification
#: with the program's LSTM, AdamW, one client at a time)
SEQCLS = Path(__file__).resolve().parent / "data" / "seqcls"
#: the real cells, each with a small mix of the same shape
CELLS = {"femnist_cnn.uniform_b50": [[8, 1.0]],
         "fedavg_2nn.ragged_b10_50": [[4, 0.5], [8, 0.5]]}


def small_mix(name, batch_mix):
    mix = json.loads((FEDBENCH / "traffic" / f"{name}.json").read_text())
    mix.update(name=f"{name}_small", pool_clients=40, participants_per_round=8,
               local_steps=2, batch_mix=batch_mix, test_samples=32)
    return mix


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark with the program beside it, and a
    ``add_cell`` that adds a cell by writing new files only."""
    root = tmp_path / "checkout"
    shutil.copytree(FEDBENCH, root / "fedbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(ROOT / "src")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    before = {p: p.read_bytes() for p in (root / "fedbench").rglob("*") if p.is_file()}

    def add_cell(cell, config, mix, limits):
        bench = json.loads((root / "BENCHMARK.json").read_text())
        name = f"{config}.{mix['name']}"
        (root / "fedbench" / "traffic" / f"{mix['name']}.json").write_text(json.dumps(mix))
        (root / "fedbench" / "limits" / f"{name}.json").write_text(json.dumps(limits))
        bench["workloads"].append({"name": name, "config": config, "traffic": mix["name"],
                                   "chips": 1, "why": f"small copy of {cell}"})
        (root / "BENCHMARK.json").write_text(json.dumps(bench))
        return name

    def unchanged_files():
        return all(p.read_bytes() == b for p, b in before.items())

    return root, add_cell, unchanged_files


def run_cell(root, name, seed=987654321012, trace=0):
    lines = []
    rc = run.main(["--workload", name, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace)], root=root, require_tpu=False, emit=lines.append)
    assert rc == 0
    return json.loads(lines[-1])


def test_new_cell_from_new_files(checkout):
    root, add_cell, unchanged = checkout
    (root / "fedbench" / "metrics" / "rounds_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "rounds_in_window", "unit": "count", "better": "higher",
                               "source": "host_clock", "layer": "round control",
                               "moves": "client_updates_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    strict = {k: {"limit": 1e-3} for k in replay.NUMBERS}
    name = add_cell("fedavg_2nn.ragged_b10_50", "fedavg_2nn",
                    small_mix("ragged_b10_50", [[4, 0.5], [8, 0.5]]), strict)
    res = run_cell(root, name, trace=1)
    assert unchanged()
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["rounds_in_window"]["value"] >= 1
    # listed for its own cells only
    assert "compiles_in_window" not in res["metrics"]
    assert list(res)[-1] == "checks"
    res = run_cell(root, name, trace=0)
    assert set(res["metrics"]) >= {"client_updates_per_s", "setup_s"}
    assert res["correct"] is True and res["failed"] == 0


def add_seqcls(root, add_cell):
    """The test-only family, its reference, configuration and cell, added
    to the checkout by new files and new entries of ``BENCHMARK.json``."""
    fb = root / "fedbench"
    shutil.copy(SEQCLS / "family.py", fb / "families" / "seqcls.py")
    shutil.copy(SEQCLS / "reference.py", fb / "reference" / "lstm.py")
    shutil.copy(SEQCLS / "config.json", fb / "configs" / "lstm_tiny.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "lstm_tiny", "source": "https://aclanthology.org/D13-1170",
                             "file": "fedbench/configs/lstm_tiny.json", "reduced": [],
                             "why": "the program's LSTM on SST-2-shaped sentences"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return add_cell("lstm_tiny.adamw_b8", "lstm_tiny",
                    json.loads((SEQCLS / "traffic.json").read_text()),
                    json.loads((SEQCLS / "limits.json").read_text()))


def test_new_family_from_new_files(checkout, monkeypatch):
    """A family whose data, model, reference and local rule the image
    family does not have (token sequences, the LSTM, AdamW, clients one
    at a time) runs, checks correct, and catches a planted fault, with no
    file of the benchmark edited."""
    root, add_cell, unchanged = checkout
    name = add_seqcls(root, add_cell)
    res = run_cell(root, name)
    assert unchanged()
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["run"]["warmed_compositions"] == 0
    _plant(monkeypatch, "half_batch")
    res = run_cell(root, name, seed=135792468013)
    assert res["correct"] is False, res["checks"]


def test_refuses_off_tpu(capsys):
    lines = []
    rc = run.main(["--workload", "fedavg_2nn.ragged_b10_50", "--seed", "1", "--seconds", "1"],
                  emit=lines.append)
    assert rc != 0 and not lines
    assert "not a TPU" in capsys.readouterr().err


def test_refuses_unknown_device_kind(monkeypatch, capsys):
    class Dev:
        platform, device_kind = "tpu", "TPU v99 unknown"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    lines = []
    rc = run.main(["--workload", "fedavg_2nn.ragged_b10_50", "--seed", "1", "--seconds", "1"],
                  emit=lines.append)
    assert rc != 0 and not lines
    assert "not in peaks.json" in capsys.readouterr().err


def test_refuses_a_cell_without_limits(checkout, capsys):
    root, add_cell, _ = checkout
    name = add_cell("fedavg_2nn.ragged_b10_50", "fedavg_2nn",
                    small_mix("ragged_b10_50", [[4, 0.5], [8, 0.5]]),
                    {"loss_gap": {"limit": 1e-3}})
    lines = []
    rc = run.main(["--workload", name, "--seed", "1", "--seconds", "1"],
                  root=root, require_tpu=False, emit=lines.append)
    assert rc != 0 and not lines
    assert "no limit for update_gap" in capsys.readouterr().err


def test_rounds_after_window_reach_both_ends(checkout):
    """The rounds checked after the window run at the fewest and the most
    clients of the first batch size that set-up warms."""
    root, add_cell, _ = checkout
    mix = small_mix("ragged_b10_50", [[4, 0.5], [8, 0.5]])
    strict = {k: {"limit": 1e-3} for k in replay.NUMBERS}
    name = add_cell("fedavg_2nn.ragged_b10_50", "fedavg_2nn", mix, strict)
    spec = run.load_cell(name, root)
    seed = 2 ** 33 + 17
    tr, pool, _ = run.build_trainer(spec, seed)
    theta0 = jax.device_get(tr.params["main"])
    after = run.rounds_after_window(tr, pool, spec["traffic"], seed, theta0)
    comps = generate.composition_range(mix)
    got = [sum(int(pool.batch_sizes[c] == 4) for c in rnd.cids) for rnd in after.rounds]
    assert got == [comps[0][0], comps[-1][0]] and comps[0] != comps[-1]
    assert all(len(rnd.cids) == mix["participants_per_round"] for rnd in after.rounds)
    assert "_sample" not in vars(tr)
    assert set(after.program.client_deltas) == {0, 1}


def test_refuses_without_the_program(tmp_path, capsys):
    shutil.copytree(FEDBENCH, tmp_path / "fedbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    lines = []
    rc = run.main(["--workload", "fedavg_2nn.ragged_b10_50", "--seed", "1", "--seconds", "1"],
                  root=tmp_path, emit=lines.append)
    assert rc != 0 and not lines


class _HalfData:
    """A client's data whose every batch loses its second half."""

    def __init__(self, data):
        self._data, self.batch_size = data, data.batch_size

    def batches(self, n):
        for b in self._data.batches(n):
            yield {k: v[: len(v) // 2] for k, v in b.items()}


class _HalfClient:
    def __init__(self, c):
        self.client_id, self.data = c.client_id, _HalfData(c.data)


def _plant(monkeypatch, fault):
    from repro.fed.batch_exec import BatchedExecutor
    from repro.fed.client import FLClient

    orig = BatchedExecutor.run_wave
    if fault == "half_batch":
        # clients trained one at a time lose the same half
        train_local = FLClient.train_local
        monkeypatch.setattr(FLClient, "train_local", lambda self, *a, **kw: train_local(
            dataclasses.replace(self, data=_HalfData(self.data)), *a, **kw))

    def broken(self, gp, clients, n_steps, round_idx=0):
        if fault == "half_batch":
            return orig(self, gp, [_HalfClient(c) for c in clients], n_steps, round_idx)
        out = orig(self, gp, clients, n_steps, round_idx)
        if fault == "one_sign":
            # one client's answer altered where it is produced: its delta
            # leaves the executor with the opposite sign
            i = len(out) // 2
            out[i] = (jax.tree.map(np.negative, out[i][0]), out[i][1], out[i][2])
            return out
        return [(jax.tree.map(np.zeros_like, d), w, m) for d, w, m in out]

    monkeypatch.setattr(BatchedExecutor, "run_wave", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "one_sign"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_planted_fault_is_not_correct(checkout, monkeypatch, cell, fault):
    """The timed path broken underneath: a step that returns its state
    unchanged, half of each batch left out with the mean taken over the
    rest, or one client's delta with its sign flipped.  Held to the real
    cell's limits, the run is not correct."""
    root, add_cell, _ = checkout
    config, mix = cell.split(".")
    limits = json.loads((FEDBENCH / "limits" / f"{cell}.json").read_text())
    name = add_cell(cell, config, small_mix(mix, CELLS[cell]), limits)
    _plant(monkeypatch, fault)
    res = run_cell(root, name)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_bf16_control_is_not_correct(checkout, cell):
    """The control: the reference in bfloat16 throughout, put in the
    program's place, held to the real cell's limits."""
    root, add_cell, _ = checkout
    config, mix = cell.split(".")
    limits = json.loads((FEDBENCH / "limits" / f"{cell}.json").read_text())
    name = add_cell(cell, config, small_mix(mix, CELLS[cell]), limits)
    spec = run.load_cell(name, root)
    seed = 24681357911
    tr, pool, ref = run.build_trainer(spec, seed)
    theta0 = jax.device_get(tr.params["main"])
    rec = replay.Recorder(theta0, 3)
    run.checked_rounds(tr, rec, 3)
    after = run.rounds_after_window(tr, pool, spec["traffic"], seed, theta0)
    m, steps, lr = spec["config"]["model"], tr.fed.local_steps, tr.fed.learning_rate
    n = after.n_rounds

    def follow(rounds, k=1, **kw):
        return replay.follow(ref, m, theta0, rounds, steps, lr, pool.test,
                             norms_rounds=range(k), **kw)

    ctl = [follow(rec.rounds, dtype="bfloat16", precision=None),
           follow(after.rounds, n, dtype="bfloat16", precision=None, delta_rounds=range(n))]
    ref_r = [follow(rec.rounds), follow(after.rounds, n, against=[after.program, ctl[1]])]
    sound = replay.judge(replay.compare([rec.program, after.program], ref_r), limits)
    assert replay.passed(sound), sound
    checks = replay.judge(replay.compare(ctl, ref_r), limits)
    assert not replay.passed(checks), checks
