"""Operations and least bytes of one client's local step, from shapes.

A model kind's reference (``reference/<kind>.py``) lists the multiply-adds
of each convolution and matmul of its forward pass per example
(``layer_macs``).  A training step needs, per example and layer, the
forward product (2 FLOPs per multiply-add), the weight gradient (2) and,
except for the first layer, whose input is data, the input gradient (2).
Nothing is recomputed; elementwise work is not counted.

The least bytes of a step are the work the algorithm needs whatever the
implementation: the client's parameters read once and written once, plus
its batch read once, as the family feeds it.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def forward_flops_per_example(ref, m: Dict[str, Any]) -> float:
    return float(sum(2 * macs for _, macs, _ in ref.layer_macs(m)))


def train_flops_per_example(ref, m: Dict[str, Any]) -> float:
    return float(sum((4 if first else 6) * macs for _, macs, first in ref.layer_macs(m)))


def n_params(ref, m: Dict[str, Any]) -> int:
    import jax

    shapes = jax.eval_shape(lambda k: ref.init(k, m), jax.random.key(0))
    return int(sum(np.prod(s.shape) for s in jax.tree.leaves(shapes)))


class StepCounts:
    """Totals over the client steps a window completed.  ``example_bytes``
    is one example as the program feeds it (the family's
    ``example_bytes``)."""

    def __init__(self, ref, m: Dict[str, Any], example_bytes: int, param_bytes: int = 4):
        self.flops_per_example = train_flops_per_example(ref, m)
        self.param_rw_bytes = 2 * param_bytes * n_params(ref, m)
        self.example_bytes = example_bytes
        self.examples = 0
        self.steps = 0

    def add(self, steps: int, batch: int) -> None:
        self.steps += steps
        self.examples += steps * batch

    @property
    def flops(self) -> float:
        return self.flops_per_example * self.examples

    @property
    def least_bytes(self) -> float:
        return float(self.param_rw_bytes * self.steps + self.example_bytes * self.examples)

    def least_seconds(self, peak: Dict[str, float]) -> float:
        return max(self.flops / peak["bf16_flops_per_s"],
                   self.least_bytes / peak["hbm_bytes_per_s"])
