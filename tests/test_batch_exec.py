"""Batched client execution (`repro.fed.batch_exec`): one compiled
program per COLLECT wave.

Acceptance pins (ISSUE 8):
* per-client results from a batched wave match running the same clients
  through the sequential path — bit-identical on the dense vmap path,
  documented-allclose on the ragged grouped_matmul path;
* ragged-wave edge cases: empty wave, single-client wave (sequential
  fallback, bit-identical by construction), zero-example client group
  (exactly-zero delta and metrics), wave larger than
  ``participants_per_round`` (``collect_wave_eager`` honors the finisher
  cap);
* trainer-level equivalence: ``client_batching="wave"`` reproduces the
  ``"off"`` path bit for bit, standalone and fabric-driven;
* the compiled wave program is reused across waves (envelope cache), and
  ``make_small_step`` is shared across callers (step cache).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - dev extra not installed
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.budget import WorkloadSpec, uniform_budgets
from repro.core.fabric import PoolFabric
from repro.core.runtime import FixedRuntime
from repro.data.pipeline import ClientDataset
from repro.fed.batch_exec import BatchedExecutor
from repro.fed.client import (
    FLClient,
    clear_step_cache,
    make_small_step,
    step_cache_stats,
)
from repro.fed.trainer import FedConfig, FederatedTrainer, RoundPhase, build_fl_clients
from repro.models.small import SmallModelConfig, init_small
from repro.optim.optimizers import make_optimizer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MCFG = SmallModelConfig(kind="mlp", hidden=16, n_layers=2, image_size=8,
                        channels=1, n_classes=10)


def _world(batch_sizes, seed=0, dtype=np.float32, samples_per_client=16):
    """Synthetic FL world; call twice with one seed to get twin worlds
    whose ClientDatasets replay identical shuffle streams."""
    rng = np.random.default_rng(seed)
    clients = []
    for i, bs in enumerate(batch_sizes):
        x = rng.normal(size=(samples_per_client, MCFG.image_size,
                             MCFG.image_size, MCFG.channels)).astype(dtype)
        y = rng.integers(0, MCFG.n_classes, size=samples_per_client).astype(np.int32)
        clients.append(FLClient(i, 100.0, ClientDataset(x, y, bs, seed=seed + i),
                                WorkloadSpec()))
    params = init_small(jax.random.PRNGKey(seed), MCFG)
    return clients, params


def _sequential(clients, params, opt, steps):
    step = make_small_step(MCFG, opt, 0.0)
    return [c.train_local(params, step, opt, n_steps=steps) for c in clients]


def _max_delta_diff(res_a, res_b):
    return max(
        float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
        for (da, _, _), (db, _, _) in zip(res_a, res_b)
        for a, b in zip(jax.tree.leaves(da), jax.tree.leaves(db))
    )


OPT = make_optimizer("sgd", 0.1)


# ------------------- wave edge cases ----------------------------------------


def test_empty_wave_returns_empty():
    ex = BatchedExecutor(MCFG, OPT)
    _, params = _world([4])
    assert ex.run_wave(params, [], 3) == []
    assert ex.stats.waves == 0  # an empty wave is not a wave


def test_single_client_wave_is_sequential_and_bit_identical():
    ex = BatchedExecutor(MCFG, OPT)
    cl, params = _world([4], seed=3)
    batched = ex.run_wave(params, cl, 3)
    cl2, params2 = _world([4], seed=3)
    seq = _sequential(cl2, params2, OPT, 3)
    assert ex.last_wave["mode"] == "seq"
    assert ex.stats.seq_clients == 1
    assert _max_delta_diff(batched, seq) == 0.0
    assert batched[0][1] == seq[0][1]  # n_seen


def test_dense_wave_bit_identical_to_sequential():
    ex = BatchedExecutor(MCFG, OPT)
    cl, params = _world([4] * 6, seed=5)
    batched = ex.run_wave(params, cl, 3, round_idx=2)
    cl2, params2 = _world([4] * 6, seed=5)
    seq = _sequential(cl2, params2, OPT, 3)
    assert ex.last_wave["mode"] == "dense"
    assert _max_delta_diff(batched, seq) == 0.0
    for (_, nb, mb), (_, ns, ms) in zip(batched, seq):
        assert nb == ns
        for k in ms:
            assert mb[k] == pytest.approx(ms[k], abs=1e-6)


def test_ragged_wave_matches_sequential_allclose():
    ex = BatchedExecutor(MCFG, OPT)
    cl, params = _world([2, 4, 6, 8], seed=7)
    batched = ex.run_wave(params, cl, 3, round_idx=1)
    cl2, params2 = _world([2, 4, 6, 8], seed=7)
    seq = _sequential(cl2, params2, OPT, 3)
    assert ex.last_wave["mode"] == "ragged"
    # grouped matmuls change summation order: allclose, not bit-identical
    # (tolerance documented in docs/architecture.md § batched executor)
    assert _max_delta_diff(batched, seq) < 1e-5
    for (_, nb, _), (_, ns, _) in zip(batched, seq):
        assert nb == ns


def test_ragged_zero_example_client_gets_exact_zero_delta():
    ex = BatchedExecutor(MCFG, OPT)
    cl, params = _world([4, 0, 6], seed=9)
    batched = ex.run_wave(params, cl, 2)
    assert ex.last_wave["mode"] == "ragged"
    delta, n_seen, metrics = batched[1]
    assert n_seen == 0
    assert all(v == 0.0 for v in metrics.values())
    assert all(not np.any(np.asarray(l)) for l in jax.tree.leaves(delta))
    # the populated clients still match their sequential runs
    cl2, params2 = _world([4, 0, 6], seed=9)
    seq = _sequential([cl2[0], cl2[2]], params2, OPT, 2)
    assert _max_delta_diff([batched[0], batched[2]], seq) < 1e-5


# ------------------- the device-resident hand-off ---------------------------


@pytest.mark.parametrize("sizes,mode", [([4] * 5, "dense"), ([4, 0, 6, 2], "ragged")])
def test_wave_deltas_stay_on_device_equal_to_the_stacked_rows(sizes, mode):
    """A batched wave hands each client's delta over as device arrays,
    equal bit for bit to that client's row of the wave program's stacked
    output; a zero-row ragged client's delta is exactly zero."""
    ex = BatchedExecutor(MCFG, OPT)
    cl, params = _world(sizes, seed=13)
    res = ex.run_wave(params, cl, 2, round_idx=4)
    assert ex.last_wave["mode"] == mode and len(res) == len(sizes)
    cl, params = _world(sizes, seed=13)
    pulled = [list(c.data.batches(2)) for c in cl]
    prepare = ex._prepare_dense if mode == "dense" else ex._prepare_ragged
    fn, host = prepare(cl, pulled, 4)
    stacked, _ = jax.device_get(fn(params, *host))
    for i, (delta, n_seen, _) in enumerate(res):
        for a, b in zip(jax.tree.leaves(delta), jax.tree.leaves(stacked)):
            assert isinstance(a, jax.Array)
            assert np.array_equal(np.asarray(a), b[i])
        if sizes[i] == 0:
            assert n_seen == 0.0
            assert all(not np.any(np.asarray(a)) for a in jax.tree.leaves(delta))


def test_unstack_keeps_only_the_wave_clients_rows():
    """Mesh-pad filler (rows past the wave's clients) never reaches the
    per-client results, and the unstack program is the stacked shape's,
    not the client count's."""
    from repro.fed.batch_exec import _unstack

    ex = BatchedExecutor(MCFG, OPT)
    stacked = {"w": jax.numpy.arange(8 * 3.0).reshape(8, 3),
               "b": jax.numpy.arange(8.0)}
    metrics = {"train_loss": jax.numpy.arange(8.0)}
    pulled = [[{"x": np.zeros((2, 1))}]] * 6      # 6 clients, 2 filler rows
    out = ex._split(stacked, metrics, [None] * 6, pulled)
    assert len(out) == 6
    for i, (t, n_seen, m) in enumerate(out):
        assert t["w"].shape == (3,) and t["b"].shape == ()
        assert np.array_equal(np.asarray(t["w"]), np.asarray(stacked["w"][i]))
        assert float(t["b"]) == i and m == {"train_loss": float(i)} and n_seen == 2.0
    built = _unstack._cache_size()
    ex._split(stacked, metrics, [None] * 5, pulled[:5])
    assert _unstack._cache_size() == built


@pytest.mark.parametrize("sizes", [[4] * 5, [4, 0, 6, 2]], ids=["dense", "ragged"])
def test_wave_moves_only_the_metrics_to_the_host(sizes):
    from repro.obs import ObsPlane

    obs = ObsPlane()
    ex = BatchedExecutor(MCFG, OPT, obs=obs, tenant="t")
    cl, params = _world(sizes, seed=2)
    res = ex.run_wave(params, cl, 2)
    n_metrics = len(res[0][2])
    assert obs.registry.counter("client.d2h_bytes", "t").value == len(sizes) * n_metrics * 4


def test_wave_program_cache_reused_across_row_splits():
    """Group sizes are traced, so two ragged waves with the same
    (clients, steps, rows, width) envelope but different per-client row
    splits share ONE compiled program."""
    ex = BatchedExecutor(MCFG, OPT)
    cl, params = _world([2, 4, 6, 8], seed=1)   # 20 rows/step
    ex.run_wave(params, cl, 2)
    cl, params = _world([8, 6, 4, 2], seed=2)   # same envelope, new split
    ex.run_wave(params, cl, 2)
    assert ex.stats.compiles == 1
    assert ex.stats.cache_hits == 1
    assert ex.last_wave["cache_hit"] is True


def test_non_mlp_heterogeneous_wave_falls_back_sequential():
    cfg = SmallModelConfig(kind="cnn", hidden=8, n_layers=1, image_size=8,
                           channels=1, n_classes=10)
    ex = BatchedExecutor(cfg, OPT)
    rng = np.random.default_rng(0)
    clients = []
    for i, bs in enumerate([2, 4]):
        x = rng.normal(size=(8, 8, 8, 1)).astype(np.float32)
        y = rng.integers(0, 10, size=8).astype(np.int32)
        clients.append(FLClient(i, 100.0, ClientDataset(x, y, bs, seed=i),
                                WorkloadSpec()))
    params = init_small(jax.random.PRNGKey(0), cfg)
    ex.run_wave(params, clients, 2)
    assert ex.last_wave["mode"] == "seq"
    assert ex.stats.seq_clients == 2


# ------------------- property: batched == sequential across dtypes ----------


@settings(max_examples=6, deadline=None)
@given(
    n_clients=st.integers(2, 4),
    batch_size=st.sampled_from([2, 4]),
    steps=st.integers(1, 2),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    seed=st.integers(0, 1000),
)
def test_property_batched_params_match_sequential(n_clients, batch_size,
                                                  steps, dtype, seed):
    np_dtype = jax.numpy.dtype(dtype)
    ex = BatchedExecutor(MCFG, OPT)
    cl, params = _world([batch_size] * n_clients, seed=seed, dtype=np_dtype)
    batched = ex.run_wave(params, cl, steps, round_idx=seed % 7)
    cl2, params2 = _world([batch_size] * n_clients, seed=seed, dtype=np_dtype)
    seq = _sequential(cl2, params2, OPT, steps)
    assert ex.last_wave["mode"] == "dense"
    diff = _max_delta_diff(batched, seq)
    if dtype == "float32":
        assert diff == 0.0  # vmap over identical per-client programs
    else:
        assert diff < 1e-2  # bf16 inputs: promotion order may differ


# ------------------- step cache (satellite) ---------------------------------


def test_make_small_step_shared_across_callers():
    clear_step_cache()
    opt = make_optimizer("sgd", 0.3)
    s1 = make_small_step(MCFG, opt, 0.0)
    s2 = make_small_step(MCFG, make_optimizer("sgd", 0.3), 0.0)
    assert s1 is s2  # same (mcfg, optimizer key, prox): one compiled step
    assert make_small_step(MCFG, opt, 0.1) is not s1  # prox changes the key
    stats = step_cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 2
    # optimizers without a cache key (e.g. LR schedules) stay private
    uncached = opt._replace(cache_key=None)
    assert make_small_step(MCFG, uncached, 0.0) is not s1
    assert step_cache_stats()["uncacheable"] == 1


# ------------------- trainer integration ------------------------------------

_TENANT_KW = dict(mirror=True, record_campaign_timeline=False,
                  record_events=False)


def _mk_trainer(engine=None, **fed_kw):
    mcfg = SmallModelConfig(kind="mlp", n_classes=10, hidden=32, n_layers=2,
                            image_size=28, channels=1)
    budgets = uniform_budgets([10, 25, 40, 55, 70, 85, 100, 30])
    clients, test = build_fl_clients(
        mcfg, budgets, "femnist", n_samples=1200, batch_size=16, n_batches=4,
        seed=1,
    )
    for c in clients:
        c.data.y = c.data.y % 10
    test["y"] = test["y"] % 10
    fed_kw.setdefault("rounds", 3)
    fed_kw.setdefault("participants_per_round", 5)
    fed = FedConfig(local_steps=2, learning_rate=0.2, **fed_kw)
    return FederatedTrainer(mcfg, clients, fed, test_batch=test, engine=engine,
                            runtime=FixedRuntime(2.0, 1.0))


def _digest(params):
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_trainer_wave_batching_bit_identical_to_off():
    off = _mk_trainer(client_batching="off")
    hist_off = off.run()
    wave = _mk_trainer(client_batching="wave")
    hist_wave = wave.run()
    assert _digest(wave.params) == _digest(off.params)
    assert hist_wave == hist_off
    assert wave.comm_bytes == off.comm_bytes  # compression seeds unchanged
    assert wave.batch_exec.stats.waves > 0
    assert wave.batch_exec.stats.dense_clients > 0


def test_trainer_wave_batching_with_int8_compression_identical():
    off = _mk_trainer(client_batching="off", compression="int8")
    hist_off = off.run()
    wave = _mk_trainer(client_batching="wave", compression="int8")
    hist_wave = wave.run()
    assert hist_wave == hist_off
    assert wave.comm_bytes == off.comm_bytes


def test_fabric_driven_wave_bit_identical_to_legacy_off():
    """The ISSUE 7 golden pin must survive batching: a fabric-driven
    trainer with ``client_batching="wave"`` reproduces the legacy
    synchronous ``run()`` with batching off, bit for bit."""
    legacy = _mk_trainer(client_batching="off")
    hist_legacy = legacy.run()

    fab = PoolFabric(total_slots=32, capacity=100.0, lease_ttl=5.0)
    eng = fab.add_tenant("solo", weight=1.0, **_TENANT_KW)
    tr = _mk_trainer(engine=eng, client_batching="wave")
    hist_fab = fab.run_trainers({"solo": tr})["solo"]

    assert _digest(tr.params) == _digest(legacy.params)
    assert hist_fab == hist_legacy
    assert tr.comm_bytes == legacy.comm_bytes
    assert tr.batch_exec.stats.waves > 0


def test_collect_wave_eager_caps_at_participants_per_round():
    """A wave larger than ``participants_per_round`` (over-selection) must
    only train the finisher cap — extra completions never enter the wave."""
    fab = PoolFabric(total_slots=32, capacity=100.0, lease_ttl=5.0)
    eng = fab.add_tenant("solo", weight=1.0, **_TENANT_KW)
    tr = _mk_trainer(engine=eng, client_batching="wave", rounds=1,
                     over_select_frac=0.4)  # 7 sampled, cap stays 5

    st = tr.begin_round()
    tr.step_round(st)
    tr.submit_round(st)
    fab._reconcile_pool()
    # pump simulated completions WITHOUT collecting until more clients
    # than the cap have finished
    while len(st.trainable) < 6 and st.phase is RoundPhase.SIMULATE:
        eng.step()
    assert len(st.trainable) >= 6
    trained = tr.collect_wave_eager(st)
    assert trained == 5  # the cap, not the wave size
    assert tr.collect_wave_eager(st) == 0  # cap reached: nothing left
    while st.phase is RoundPhase.SIMULATE and eng.peek_time() is not None:
        eng.step()
    while tr.step_round(st) is not RoundPhase.DONE:
        pass
    assert st.rec["completed"] == 5


# ------------------- shard_map path (multi-device subprocess) ---------------


def test_dense_wave_shard_map_matches_unsharded_subprocess():
    """Dense wave under a 4-device mesh (client axis sharded via the
    ``repro.dist`` rules, non-divisible wave padded) must match the
    single-device vmap program exactly."""
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import jax, numpy as np
from jax.sharding import Mesh
from test_batch_exec import MCFG, OPT, _world, _max_delta_diff
from repro.fed.batch_exec import BatchedExecutor

mesh = Mesh(np.array(jax.devices()), ('data',))
plain = BatchedExecutor(MCFG, OPT)
sharded = BatchedExecutor(MCFG, OPT, mesh=mesh)
cl, params = _world([4] * 6, seed=11)          # 6 clients -> pad to 8
a = plain.run_wave(params, cl, 3, round_idx=1)
cl, params = _world([4] * 6, seed=11)
b = sharded.run_wave(params, cl, 3, round_idx=1)
assert plain.last_wave['mode'] == sharded.last_wave['mode'] == 'dense'
assert len(a) == len(b) == 6                   # the filler is not a client
diff = _max_delta_diff(a, b)
print('DIFF', diff)
assert diff == 0.0, diff
"""
    env = dict(os.environ,
               PYTHONPATH=SRC + os.pathsep + os.path.dirname(__file__))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "DIFF" in out.stdout and out.returncode == 0, \
        out.stdout[-2000:] + out.stderr[-2000:]
