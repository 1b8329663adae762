"""The traffic generator on the CPU: what every seed shares, the device
memory set-up's data takes, and the draws of every real cell, pinned."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import generate
import run

FEDBENCH = Path(__file__).resolve().parents[1]
image = run.load_family("image", FEDBENCH.parent)


def small_pool(seed, n=60):
    mix = json.loads((FEDBENCH / "traffic" / "ragged_b10_50.json").read_text())
    mix.update(pool_clients=n, test_samples=16)
    config = json.loads((FEDBENCH / "configs" / "fedavg_2nn.json").read_text())
    return image.make_pool(seed, mix, config), mix, config


def test_every_seed_has_the_same_sets_in_another_order():
    a, mix, config = small_pool(5)
    b, _, _ = small_pool(2 ** 40 + 3)
    sizes_a = np.asarray([len(x) for x in a.x])
    sizes_b = np.asarray([len(x) for x in b.x])
    assert sorted(sizes_a) == sorted(sizes_b) and list(sizes_a) != list(sizes_b)
    assert sizes_a.sum() == 60 * config["samples_per_client"]
    assert sorted(a.budgets) == sorted(b.budgets)
    assert sorted(a.batch_sizes) == sorted(b.batch_sizes)
    for x, y in zip(a.x, a.y):
        assert x.shape[1:] == (28, 28, 1) and len(x) == len(y)
        assert y.min() >= 0 and y.max() < config["model"]["n_classes"]
    # the same seed gives the same pool
    c, _, _ = small_pool(5)
    assert all(np.array_equal(p, q) for p, q in zip(a.x, c.x))


def test_shard_sizes_keep_mean_and_spread():
    sizes = generate.shard_sizes(2800, 64.0, 25.09)
    assert sizes.sum() == 2800 * 64
    assert abs(sizes.std() - 25.09) < 1.0
    # a lognormal's low tail: no writer holds a handful of samples
    assert sizes.min() >= 10


def test_images_never_take_more_than_a_chunk_on_the_device(monkeypatch):
    """Set-up's own device memory stays at one chunk of images, far below
    what the program under test holds, whatever the pool's size."""
    # the real chunk: 8192 FEMNIST images, 25.7 MB
    assert image.CHUNK_ROWS * 28 * 28 * 4 <= 32 * 2 ** 20
    outs = []
    real = image._chunk_fn

    def watched(*shape):
        draw = real(*shape)

        def call(*a):
            out = draw(*a)
            outs.append(out.nbytes)
            return out

        return call

    monkeypatch.setattr(image, "CHUNK_ROWS", 64)
    monkeypatch.setattr(image, "_chunk_fn", watched)
    labels = np.arange(150, dtype=np.int32) % 62
    data = {"image_size": 28, "channels": 1, "classes": 62}
    x = image.make_images(11, labels, data)
    assert x.shape == (150, 28, 28, 1) and np.isfinite(x).all()
    assert len(outs) == 3 and max(outs) == 64 * 28 * 28 * 4
    # chunks are keyed by their index: a longer pool keeps the first images
    y = image.make_images(11, np.concatenate([labels, labels]), data)
    assert np.array_equal(x, y[:150])


def digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


#: what the harness draws from seed 2**33 + 424242 in each real cell at
#: its full size, as digests of the bytes: read from ``generate.make_pool``
#: and ``run.build_trainer`` before the image family had a module of its own
PINNED = {
    "femnist_cnn.uniform_b50": {
        "x": "4c1da69727fabe33", "y": "6cd595021681668e", "clients": "9d0e16a2e99cfcd1",
        "test": "ad034bb4141b32a5", "theta0": "16220d1ec0389f1d",
        "participants": "a73efb6dbf4a794d"},
    "fedavg_2nn.ragged_b10_50": {
        "x": "4c1da69727fabe33", "y": "904fc8f52e7b442d", "clients": "a90287c8fcd80533",
        "test": "c13797e0387b1e4a", "theta0": "78f1fbea90e05b7b",
        "participants": "375fde1c10ee2b1d"},
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_every_draw_of_a_real_cell_is_pinned(cell):
    """The pool, the initial weights and the participants of both rounds
    checked after the window are byte for byte what they were."""
    seed = 2 ** 33 + 424242
    spec = run.load_cell(cell, FEDBENCH.parent)
    tr, pool, _ = run.build_trainer(spec, seed)
    groups = generate.check_participants(seed, spec["traffic"], pool.batch_sizes)
    assert {
        "x": digest(pool.x), "y": digest(pool.y),
        "clients": digest([pool.batch_sizes, pool.budgets, pool.data_seeds,
                           np.int64(pool.fed_seed), np.int64(pool.weight_key)]),
        "test": digest([pool.test["x"], pool.test["y"]]),
        "theta0": digest(jax.tree.leaves(jax.device_get(tr.params["main"]))),
        "participants": digest(groups),
    } == PINNED[cell]
