"""The traffic generator on the CPU: what every seed shares, and the
device memory set-up's data takes."""
import json
from pathlib import Path

import numpy as np

import generate

FEDBENCH = Path(__file__).resolve().parents[1]


def small_pool(seed, n=60):
    mix = json.loads((FEDBENCH / "traffic" / "ragged_b10_50.json").read_text())
    mix.update(pool_clients=n, test_samples=16)
    config = json.loads((FEDBENCH / "configs" / "fedavg_2nn.json").read_text())
    return generate.make_pool(seed, mix, config), mix, config


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
    assert generate.CHUNK_ROWS * 28 * 28 * 4 <= 32 * 2 ** 20
    outs = []
    real = generate._chunk_fn

    def watched(*shape):
        draw = real(*shape)

        def call(*a):
            out = draw(*a)
            outs.append(out.nbytes)
            return out

        return call

    monkeypatch.setattr(generate, "CHUNK_ROWS", 64)
    monkeypatch.setattr(generate, "_chunk_fn", watched)
    labels = np.arange(150, dtype=np.int32) % 62
    data = {"image_size": 28, "channels": 1, "classes": 62}
    x = generate.make_images(11, labels, data)
    assert x.shape == (150, 28, 28, 1) and np.isfinite(x).all()
    assert len(outs) == 3 and max(outs) == 64 * 28 * 28 * 4
    # chunks are keyed by their index: a longer pool keeps the first images
    y = generate.make_images(11, np.concatenate([labels, labels]), data)
    assert np.array_equal(x, y[:150])
