"""The plain references against the program, on the CPU at small sizes:
the forward pass, one local training and a whole replayed round."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import replay
from repro.models.small import SmallModelConfig, small_apply
from repro.optim.optimizers import make_optimizer
from repro.fed.client import build_step_fn

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def small(name, **kw):
    m = json.loads((CONFIGS / f"{name}.json").read_text())["model"]
    m.update(kw)
    return m


@pytest.mark.parametrize("name,kind", [("femnist_cnn", "cnn"), ("fedavg_2nn", "mlp")])
def test_forward_and_step_match_program(name, kind):
    m = small(name, hidden=32)
    ref = replay.load_reference(kind)
    params = ref.init(jax.random.key(3), m)
    x = jax.random.normal(jax.random.key(4), (6, 28, 28, 1))
    y = jnp.arange(6) % m["n_classes"]
    with jax.default_matmul_precision("highest"):
        prog = small_apply({"main": params}, SmallModelConfig(**m), x)
        mine = ref.logits(params, x, m, "highest")
        # a tolerance of float32 rounding: the two sum in different orders
        np.testing.assert_allclose(mine, prog, rtol=1e-5, atol=1e-5)
        step = jax.jit(build_step_fn(SmallModelConfig(**m), make_optimizer("sgd", 0.05)))
        p1, _, _ = step({"main": params}, {"step": jnp.zeros((), jnp.int32)},
                        {"x": x, "y": y}, {"main": params})

        def ce(p):
            z = ref.logits(p, x, m, "highest")
            return jnp.mean(jax.nn.logsumexp(z, -1)
                            - jnp.take_along_axis(z, y[:, None], -1)[:, 0])

        g = jax.grad(ce)(params)
        norm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, replay.CLIP_NORM / norm)
        mine1 = jax.tree.map(lambda a, b: a - 0.05 * scale * b, params, g)
    for a, b in zip(jax.tree.leaves(mine1), jax.tree.leaves(p1["main"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_worst_leaf_gap_rules():
    ref = np.array([1.0, 2.0, 4.0, 8.0, 1e-6])
    # the last leaf is under a thousandth of the median: left out
    assert replay.worst_leaf_gap(np.array([1.0, 2.0, 4.0, 8.0, 5.0]), ref) == 0.0
    # a leaf under the median is measured against the median leaf (2.0)
    assert replay.worst_leaf_gap(np.array([1.5, 2.0, 4.0, 8.0, 0.0]), ref) == pytest.approx(0.25)
    assert replay.worst_leaf_gap(np.zeros(5), ref) == pytest.approx(1.0)


def test_delta_turn_rules():
    norms = np.array([1.0, 2.0, 4.0])
    # the same direction reads 0, the whole delta of the opposite sign 2
    assert replay.delta_turn(np.ones(3), norms, norms) == pytest.approx(0.0, abs=1e-12)
    assert replay.delta_turn(-np.ones(3), norms, norms) == pytest.approx(2.0)
    # one leaf turned around weighs by its share of the delta: 2 * 4/21
    assert replay.delta_turn(np.array([1.0, -1.0, 1.0]), norms, norms) == pytest.approx(8 / 21)
