"""Operation and parameter counts from shapes, against hand counts."""
import json
from pathlib import Path

import pytest

import flops
from replay import load_reference

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_femnist_cnn_counts():
    m, ref = model("femnist_cnn"), load_reference("cnn")
    # per example, multiply-adds: conv1 28*28*32*9*1, conv2 14*14*64*9*32,
    # fc 3136*2048, head 2048*62
    macs = 28 * 28 * 32 * 9 + 14 * 14 * 64 * 9 * 32 + 3136 * 2048 + 2048 * 62
    assert flops.forward_flops_per_example(ref, m) == 2 * macs == 20_775_936
    # backward: weight gradients everywhere, input gradients past conv1
    assert flops.train_flops_per_example(ref, m) == 6 * macs - 2 * 28 * 28 * 32 * 9
    assert flops.n_params(ref, m) == 6_570_430


def test_fedavg_2nn_counts():
    m, ref = model("fedavg_2nn"), load_reference("mlp")
    macs = 784 * 200 + 200 * 200 + 200 * 10
    assert flops.forward_flops_per_example(ref, m) == 2 * macs
    assert flops.train_flops_per_example(ref, m) == 6 * macs - 2 * 784 * 200
    assert flops.n_params(ref, m) == 199_210


@pytest.mark.parametrize("name,kind", [("femnist_cnn", "cnn"), ("fedavg_2nn", "mlp")])
def test_step_counts_least_time(name, kind):
    m, ref = model(name), load_reference(kind)
    c = flops.StepCounts(ref, m)
    c.add(10, 50)
    p = flops.n_params(ref, m)
    assert c.examples == 500 and c.steps == 10
    assert c.least_bytes == 10 * 8 * p + 500 * (784 * 4 + 4)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert c.least_seconds(peak) == max(c.flops / 197e12, c.least_bytes / 819e9)
