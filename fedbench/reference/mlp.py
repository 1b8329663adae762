"""Plain reference of the MLP kind: McMahan et al.'s 2NN as the program
builds it (the flattened image through ``n_layers`` ReLU dense layers of
``hidden`` units and a linear head).  Straight ``jax.numpy`` at the
``precision`` it is given; the parameter tree has the program's layout
(``layers``, ``head``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dims(m):
    return [m["image_size"] * m["image_size"] * m["channels"]] + [m["hidden"]] * m["n_layers"]


def _dense(key, fan_in, fan_out, dtype):
    bound = 1.0 / math.sqrt(fan_in)
    return {"w": jax.random.uniform(key, (fan_in, fan_out), jnp.float32,
                                    -bound, bound).astype(dtype),
            "b": jnp.zeros((fan_out,), dtype)}


def init(key, m, dtype=jnp.float32):
    d = _dims(m)
    k = jax.random.split(key, m["n_layers"] + 1)
    return {"layers": [_dense(k[i], d[i], d[i + 1], dtype) for i in range(m["n_layers"])],
            "head": _dense(k[-1], d[-1], m["n_classes"], dtype)}


def logits(params, x, m, precision=None):
    h = x.reshape(x.shape[0], -1)
    for lyr in params["layers"]:
        h = jax.nn.relu(jnp.matmul(h, lyr["w"], precision=precision) + lyr["b"])
    return jnp.matmul(h, params["head"]["w"], precision=precision) + params["head"]["b"]


def layer_macs(m):
    d = _dims(m) + [m["n_classes"]]
    return [(f"dense{i + 1}", d[i] * d[i + 1], i == 0) for i in range(len(d) - 1)]
