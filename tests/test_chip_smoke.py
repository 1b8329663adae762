"""``chip_smoke.py`` and the pieces it leans on, checked on the CPU.

* the script refuses to run anywhere but on a TPU, and never prints its
  ``"ok": true`` line there;
* its phases and wave-versus-reference helpers, at a tiny size, with the
  CPU standing in for the chip;
* the compile-cache helper every entry point calls;
* ``run_multihost`` starts its workers pinned to the CPU.
"""
import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache, multihost

REPO = Path(__file__).resolve().parent.parent


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


@pytest.fixture
def cpu():
    return jax.devices("cpu")[0]


@pytest.fixture
def restore_cache_config():
    """Phases that call an entry point's ``main`` point JAX's persistent
    cache somewhere; put it back so later tests compile as before."""
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)
    compilation_cache.reset_cache()


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_the_cpu():
    out = _run_script(REPO / "chip_smoke.py", REPO)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "not a TPU" in out.stderr


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_cross_device_phase_tiny(cpu):
    lines = []
    out = smoke.cross_device_phase(cpu, n_clients=4, rounds=2, steps=2,
                                   hidden=8, log=lines.append)
    rounds = [l for l in lines if l["phase"] == "cross_device"]
    assert [r["round"] for r in rounds] == [1, 2]
    assert rounds[0]["wave"]["cache_hit"] is False
    assert rounds[1]["wave"]["cache_hit"] is True
    assert out["stats"]["compiles"] == 1 and out["stats"]["cache_hits"] == 2
    assert out["ok"] and out["platform"] == out["ref_platform"] == "cpu"
    # the same program on the same device reproduces its deltas exactly
    assert out["max_rel_delta_err"] == 0.0


def test_ragged_wave_phase_tiny(cpu):
    lines = []
    out = smoke.ragged_wave_phase(cpu, n_clients=4, steps=2, hidden=16,
                                  log=lines.append)
    assert lines == [out]
    assert out["ok"] and out["max_rel_delta_err"] <= smoke.DELTA_TOL
    assert out["rows_per_step"] == sum(smoke.RAGGED_BATCHES)


def test_replay_wave_replays_the_same_data(cpu):
    from repro.fed.batch_exec import BatchedExecutor
    from repro.models.small import SmallModelConfig, init_small
    from repro.optim.optimizers import make_optimizer

    mcfg = SmallModelConfig(kind="mlp", hidden=8, n_layers=2, n_classes=10)
    clients, _ = smoke._femnist_clients(mcfg, 3, 2, seed=1)
    opt = make_optimizer("sgd", 0.1)
    params = init_small(jax.random.PRNGKey(1), mcfg)
    wave = (params, clients, 2, 3)
    first = smoke.replay_wave(BatchedExecutor(mcfg, opt), *wave)
    again = smoke.replay_wave(BatchedExecutor(mcfg, opt), *wave, device=cpu,
                              precision=smoke.CHECK_PRECISION)
    assert smoke.deltas_close(again, first) == (True, 0.0)
    # the clients themselves were not advanced: a plain wave on them now
    # draws the same batches, and the wave after that draws new ones
    ex = BatchedExecutor(mcfg, opt)
    assert smoke.deltas_close(ex.run_wave(*wave), first) == (True, 0.0)
    ok, worst = smoke.deltas_close(ex.run_wave(*wave), first)
    assert not ok and worst > smoke.DELTA_TOL


def test_deltas_close_flags_a_wrong_client():
    d = {"w": np.ones((3, 2), np.float32), "b": np.zeros((2,), np.float32)}
    ref = [(d, 4.0, {}), (jax.tree.map(lambda a: 2 * a, d), 4.0, {})]
    near = [(jax.tree.map(lambda a: a * (1 + smoke.DELTA_TOL / 2), x), n, m)
            for x, n, m in ref]
    ok, worst = smoke.deltas_close(near, ref)
    assert ok and worst == pytest.approx(smoke.DELTA_TOL / 2)
    swapped = [ref[1], ref[0]]
    ok, worst = smoke.deltas_close(swapped, ref)
    assert not ok and worst >= 0.5
    # a zero leaf of the reference must be matched exactly
    nonzero_b = [({"w": d["w"], "b": d["b"] + 1e-6}, 4.0, {}), ref[1]]
    assert smoke.deltas_close(nonzero_b, ref) == (False, math.inf)


def test_cross_silo_phase_tiny(tmp_path, monkeypatch, restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    lines = []
    out = smoke.cross_silo_phase(reduced=True, silos=2, rounds=2,
                                 local_steps=1, batch=2, seq=16,
                                 log=lines.append)
    assert lines == [out]
    assert len(out["losses"]) == 2 and all(map(math.isfinite, out["losses"]))
    assert abs(out["first_loss"] - math.log(512)) <= 1.0
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_follows_the_environment(tmp_path, monkeypatch,
                                               restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))
    assert compile_cache.compile_cache_dir() == str(tmp_path / "cache")
    assert compile_cache.enable_compile_cache() == str(tmp_path / "cache")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cache")


def test_compile_cache_defaults_to_one_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert Path(first) == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_run_multihost_pins_workers_to_the_cpu(monkeypatch):
    import multiprocessing

    seen = []

    class FakeProcess:
        def __init__(self, target, args, daemon):
            self.args = args

        def start(self):
            seen.append((self.args[1], os.environ.get("JAX_PLATFORMS")))

        def join(self, timeout=None):
            pass

        def is_alive(self):
            return False

    class FakeContext:
        Process = FakeProcess

    class FakeTransport:
        host, port, closed = "127.0.0.1", 1, False

        def close(self):
            self.closed = True

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(multiprocessing, "get_context", lambda _m: FakeContext)
    monkeypatch.setattr(multihost, "run_server", lambda *a, **k: "trainer")
    transport = FakeTransport()
    spec = multihost.WorldSpec(n_clients=3)
    assert multihost.run_multihost(spec, transport=transport) == "trainer"
    assert seen == [(0, "cpu"), (1, "cpu"), (2, "cpu")]
    assert os.environ["JAX_PLATFORMS"] == "tpu"  # the server's own is kept
    assert transport.closed
