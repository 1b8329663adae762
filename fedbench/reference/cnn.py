"""Plain reference of the CNN kind: the LEAF FEMNIST CNN as the program
builds it (two 3x3 convolutions of 32 and 64 channels, each followed by
ReLU and a 2x2 max-pool, a ReLU dense layer of ``hidden`` units and a
linear head).  Straight ``jax.numpy``: no kernels, no batching over
clients.  Every contraction takes the ``precision`` it is given, so the
reference runs at "highest" wherever it runs.  The parameter tree has the
layout of the program's checkpoints (``convs``, ``fc``, ``head``), which
is what lets its weights be handed to the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

CHANNELS = (32, 64)
KERNEL = 3


def _dense(key, fan_in, fan_out, dtype):
    bound = 1.0 / math.sqrt(fan_in)
    return {"w": jax.random.uniform(key, (fan_in, fan_out), jnp.float32,
                                    -bound, bound).astype(dtype),
            "b": jnp.zeros((fan_out,), dtype)}


def _conv(key, cin, cout, dtype):
    bound = 1.0 / math.sqrt(KERNEL * KERNEL * cin)
    return {"w": jax.random.uniform(key, (KERNEL, KERNEL, cin, cout), jnp.float32,
                                    -bound, bound).astype(dtype),
            "b": jnp.zeros((cout,), dtype)}


def _flat(m):
    side = m["image_size"] // (2 ** len(CHANNELS))
    return side * side * CHANNELS[-1]


def init(key, m, dtype=jnp.float32):
    if m["n_layers"] != len(CHANNELS):
        raise ValueError(f"the CNN reference has {len(CHANNELS)} conv layers")
    k = jax.random.split(key, 4)
    cin = (m["channels"],) + CHANNELS[:-1]
    return {"convs": [_conv(k[i], cin[i], CHANNELS[i], dtype) for i in range(2)],
            "fc": _dense(k[2], _flat(m), m["hidden"], dtype),
            "head": _dense(k[3], m["hidden"], m["n_classes"], dtype)}


def _pool2(h):
    b, hh, ww, c = h.shape
    return h.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))


def logits(params, x, m, precision=None):
    h = x
    for conv in params["convs"]:
        h = lax.conv_general_dilated(
            h, conv["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        h = _pool2(jax.nn.relu(h + conv["b"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.matmul(h, params["fc"]["w"], precision=precision)
                    + params["fc"]["b"])
    return jnp.matmul(h, params["head"]["w"], precision=precision) + params["head"]["b"]


def layer_macs(m):
    """(layer, multiply-adds per example, is the first layer) for every
    convolution and matmul of the forward pass."""
    s = m["image_size"]
    cin = m["channels"]
    out = []
    for i, cout in enumerate(CHANNELS):
        out.append((f"conv{i + 1}", s * s * cout * KERNEL * KERNEL * cin, i == 0))
        s, cin = s // 2, cout
    out.append(("fc", _flat(m) * m["hidden"], False))
    out.append(("head", m["hidden"] * m["n_classes"], False))
    return out
