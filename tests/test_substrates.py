"""Optimizer, checkpoint, data, aggregation and compression substrate tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev extra absent: deterministic mini-sampler
    from _hypothesis_fallback import given, settings, strategies as st

from repro.ckpt.checkpoint import CheckpointManager, restore_pytree, save_pytree
from repro.core.aggregation import (FOLD_CHUNK, AsyncAggregator, apply_deltas, fedavg,
                                    tree_sub)
from repro.data.partition import dirichlet_partition, partition_stats
from repro.data.pipeline import ClientDataset
from repro.data.synthetic import make_dataset
from repro.fed.compression import compress, compressed_bytes, decompress
from repro.optim.optimizers import (
    adafactor, adamw, clip_by_global_norm, make_optimizer, momentum,
    opt_state_axes, sgd, warmup_cosine,
)


# ----------------------------- optimizers ----------------------------------


@pytest.mark.parametrize("name", ["sgd", "momentum", "adam", "adamw", "adafactor"])
def test_optimizers_converge_quadratic(name):
    opt = make_optimizer(name, 0.1)
    params = {"w": jnp.array([3.0, -2.0, 1.5])}
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        return opt.update(grads, state, params)

    for _ in range(200):
        params, state = step(params, state)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_adafactor_factored_state_is_small():
    opt = adafactor(1e-3)
    params = {"big": jnp.zeros((256, 512)), "small": jnp.zeros((4, 4))}
    state = opt.init(params)
    assert set(state["v"]["big"]) == {"vr", "vc"}
    assert state["v"]["big"]["vr"].shape == (256,)
    assert state["v"]["big"]["vc"].shape == (512,)
    assert set(state["v"]["small"]) == {"v"}


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(1000.0))
    total = jnp.sqrt(jnp.sum(clipped["a"] ** 2))
    assert float(total) == pytest.approx(1.0, rel=1e-5)


def test_warmup_cosine_shape():
    sched = warmup_cosine(1.0, warmup=10, total=100)
    assert float(sched(jnp.int32(5))) == pytest.approx(0.5)
    assert float(sched(jnp.int32(10))) == pytest.approx(1.0)
    assert float(sched(jnp.int32(100))) == pytest.approx(0.1, rel=1e-2)


def test_opt_state_axes_structures():
    p_axes = {"w": ("embed", "mlp")}
    p_shapes = {"w": jax.ShapeDtypeStruct((256, 512), jnp.float32)}
    ax = opt_state_axes("adamw", p_axes, p_shapes)
    assert ax["m"] == p_axes and ax["v"] == p_axes
    ax = opt_state_axes("adafactor", p_axes, p_shapes)
    assert ax["v"]["w"]["vr"] == ("embed",)
    assert ax["v"]["w"]["vc"] == ("mlp",)


# ----------------------------- checkpointing --------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    path = str(tmp_path / "x.npz")
    save_pytree(path, tree, {"step": 3})
    out = restore_pytree(path, tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert out["b"]["c"].dtype == np.asarray(tree["b"]["c"]).dtype


def test_manager_keep_k_and_resume(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.zeros((3,))}
    for step in (1, 2, 3, 4):
        mgr.save(step, {"w": jnp.full((3,), float(step))})
    assert mgr.steps() == [3, 4]
    step, restored = mgr.restore_latest(tree)
    assert step == 4
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full(3, 4.0))


def test_manager_skips_torn_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(1, {"w": jnp.full((3,), 1.0)})
    mgr.save(2, {"w": jnp.full((3,), 2.0)})
    # corrupt the newest file (simulated crash mid-write)
    newest = os.path.join(str(tmp_path), "ckpt_0000000002.npz")
    with open(newest, "wb") as f:
        f.write(b"garbage")
    step, restored = mgr.restore_latest({"w": jnp.zeros((3,))})
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.full(3, 1.0))


def test_async_checkpoint_writer(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=True)
    mgr.save(7, {"w": jnp.ones((4,))})
    mgr.wait()
    assert mgr.steps() == [7]


# ----------------------------- data -----------------------------------------


def test_dirichlet_partition_properties():
    _, y = make_dataset("cifar10", 2000, seed=0)
    parts = dirichlet_partition(y, 20, alpha=0.3, seed=0)
    all_idx = np.concatenate(parts)
    assert len(all_idx) == len(y)
    assert len(np.unique(all_idx)) == len(y)  # disjoint cover
    stats = partition_stats(parts, y)
    # Non-IID: mean label entropy well below uniform
    assert stats["label_entropy_mean"] < stats["label_entropy_uniform"] * 0.9


def test_client_dataset_wraps_small_shards():
    x = np.arange(5, dtype=np.float32)[:, None]
    y = np.arange(5, dtype=np.int32)
    ds = ClientDataset(x, y, batch_size=8, seed=0)
    b = ds.next_batch()
    assert b["x"].shape == (8, 1)


def test_make_dataset_shapes():
    x, y = make_dataset("femnist", 64, seed=1)
    assert x.shape == (64, 28, 28, 1) and y.max() < 62
    x, y = make_dataset("sst2", 16, seed=1)
    assert x.shape == (16, 64) and x.dtype == np.int32


# ----------------------------- aggregation ----------------------------------


def test_fedavg_weighted_mean():
    a = {"w": jnp.array([1.0, 1.0])}
    b = {"w": jnp.array([3.0, 3.0])}
    avg = fedavg([(a, 1.0), (b, 3.0)])
    np.testing.assert_allclose(np.asarray(avg["w"]), [2.5, 2.5])


def test_apply_deltas_moves_params():
    params = {"w": jnp.zeros((2,))}
    delta = {"w": jnp.ones((2,))}
    out = apply_deltas(params, [(delta, 1.0)], server_lr=0.5)
    np.testing.assert_allclose(np.asarray(out["w"]), [0.5, 0.5])


def _eager_fold(updates):
    """FedAvg's weighted sum as an eager float32 loop: scale, then add in
    client order."""
    total = sum(w for _, w in updates)
    acc = None
    for t, w in updates:
        s = np.float32(w / total)
        term = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32) * s, t)
        acc = term if acc is None else jax.tree.map(jnp.add, acc, term)
    return acc


def _mixed_updates(seed, n=5, host=False):
    rng = np.random.default_rng(seed)
    updates = []
    for _ in range(n):
        t = {"w": rng.normal(size=(6, 4)).astype(np.float32),
             "b": rng.normal(size=(4,)).astype(np.float32)}
        updates.append((t if host else jax.tree.map(jnp.asarray, t),
                        float(rng.integers(1, 300))))
    return updates


@pytest.mark.parametrize("n", [5, 2 * FOLD_CHUNK + 5], ids=["one_chunk", "three_chunks"])
@pytest.mark.parametrize("host", [False, True], ids=["device", "numpy"])
def test_fedavg_matches_eager_float32_fold(host, n):
    from repro.obs.metrics import Counter

    updates = _mixed_updates(4, n=n, host=host)
    ref = _eager_fold(updates)
    avg = fedavg(updates)
    for k in ref:
        assert isinstance(avg[k], jax.Array) and avg[k].dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(avg[k]), np.asarray(ref[k]), rtol=1e-6,
                                   atol=1e-7)
    h2d = Counter()
    params = {"w": jnp.zeros((6, 4)), "b": jnp.zeros((4,))}
    apply_deltas(params, updates, h2d=h2d)
    host_bytes = sum(a.nbytes for t, _ in updates for a in jax.tree.leaves(t))
    assert h2d.value == (host_bytes if host else 0)


def test_fold_chunk_padding_adds_exactly_nothing():
    """A short chunk is padded with its last delta at scale 0: the sum is
    bit for bit the one padded with zero trees."""
    from repro.core.aggregation import _fold_chunk

    updates = _mixed_updates(5, n=3)
    avg = fedavg(updates)
    total = sum(w for _, w in updates)
    scales = np.zeros(FOLD_CHUNK, np.float32)
    scales[:3] = [w / total for _, w in updates]
    zeros = jax.tree.map(jnp.zeros_like, updates[0][0])
    ref = _fold_chunk(None, scales, tuple([t for t, _ in updates] + [zeros] * (FOLD_CHUNK - 3)))
    for k in ref:
        assert np.array_equal(np.asarray(avg[k]), np.asarray(ref[k]))


def test_fedavg_programs_do_not_depend_on_weights_or_count():
    """New weights and another number of deltas (one chunk or several)
    reuse the fold's programs: a round whose finisher count varies builds
    nothing."""
    from repro.core.aggregation import _fold_chunk

    fedavg(_mixed_updates(1, n=FOLD_CHUNK + 1))        # first and later chunks
    built = _fold_chunk._cache_size()
    for seed, n in [(2, 3), (3, 4), (4, FOLD_CHUNK), (5, 3 * FOLD_CHUNK + 5)]:
        fedavg(_mixed_updates(seed, n=n))
    assert _fold_chunk._cache_size() == built


def test_async_buffer_staleness_discount():
    agg = AsyncAggregator(buffer_size=2, staleness_alpha=1.0, server_lr=1.0)
    agg.server_round = 2
    params = {"w": jnp.zeros((1,))}
    assert not agg.add({"w": jnp.ones((1,))}, 1.0, round_started=2)  # fresh
    assert agg.add({"w": jnp.ones((1,))}, 1.0, round_started=0)      # stale (s=2)
    out = agg.flush(params)
    # weights 1 and 1/3 -> mean = (1*1 + 1*(1/3)) / (4/3) = 1
    np.testing.assert_allclose(np.asarray(out["w"]), [1.0])


# ----------------------------- compression ----------------------------------


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 1000))
def test_int8_compression_bounded_error(seed):
    key = jax.random.PRNGKey(seed)
    delta = {"w": jax.random.normal(key, (64, 32)) * 0.01}
    comp = compress(delta, "int8", seed=seed)
    out = decompress(comp)
    scale = float(jnp.abs(delta["w"]).max()) / 127.0
    err = np.abs(np.asarray(out["w"]) - np.asarray(delta["w"])).max()
    assert err <= scale + 1e-7  # stochastic rounding: at most one quantum
    assert compressed_bytes(comp) < delta["w"].nbytes / 3


def test_topk_keeps_largest():
    delta = {"w": jnp.array([0.0, 5.0, -3.0, 0.1])}
    comp = compress(delta, "topk", k_frac=0.5)
    out = decompress(comp)
    np.testing.assert_allclose(np.asarray(out["w"]), [0.0, 5.0, -3.0, 0.0])
