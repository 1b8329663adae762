"""The plain references against the program, on the CPU at small sizes:
the forward pass, one local step, and the replay's AdamW."""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import replay
from repro.data.pipeline import ClientDataset
from repro.fed.client import FLClient, build_step_fn
from repro.models.small import SmallModelConfig, small_apply, small_loss
from repro.optim.optimizers import make_optimizer

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


def test_replay_adamw_matches_program_two_steps():
    """The replay's plain AdamW against two steps of the program's
    ``make_optimizer("adamw", lr)`` (the trainer's: no weight decay) on one
    client, both on the program's loss, so that only the local rule
    differs."""
    m = small("fedavg_2nn", hidden=32)
    cfg = SmallModelConfig(**m)
    # the program's loss in a reference's clothes: the same gradient on both sides
    ref = SimpleNamespace(loss=lambda p, batch, m, precision: small_loss({"main": p}, cfg, batch)[0])
    params = replay.load_reference("mlp").init(jax.random.key(5), m)
    x = np.asarray(jax.random.normal(jax.random.key(6), (24, 28, 28, 1)))
    data = ClientDataset(x, np.arange(24, dtype=np.int32) % 10, 8, seed=7)
    lr = 0.01
    with jax.default_matmul_precision("highest"):
        client = FLClient(0, 100.0, replay.copy.deepcopy(data))
        opt = make_optimizer("adamw", lr)
        delta, _, _ = client.train_local({"main": params}, jax.jit(build_step_fn(cfg, opt)),
                                         opt, n_steps=2)
        out = replay.follow(ref, m, params, [replay.CheckedRound([0], [data])], 2, lr,
                            {"x": x[:4], "y": np.zeros(4, np.int32)}, optimizer="adamw",
                            delta_rounds=(0,))
    mine = out.client_deltas[0][0]
    # Adam moves an entry by about lr a step
    assert max(float(np.max(np.abs(a))) for a in jax.tree.leaves(mine)) > 1.5 * lr
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(delta["main"])):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


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
