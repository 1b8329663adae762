"""Plain reference of the LSTM kind, for the harness's tests only (copied
to ``fedbench/reference/lstm.py`` of a checkout's copy): token embeddings,
``n_layers`` LSTM layers of ``hidden`` units (the forget gate's bias
offset by 1), the mean over time and a linear head, as the program builds
it.  Straight ``jax.numpy``, one time step after another; the parameter
tree has the program's layout (``embed``, ``cells``, ``head``)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dense(key, fan_in, fan_out, dtype):
    bound = 1.0 / math.sqrt(fan_in)
    return {"w": jax.random.uniform(key, (fan_in, fan_out), jnp.float32,
                                    -bound, bound).astype(dtype),
            "b": jnp.zeros((fan_out,), dtype)}


def init(key, m, dtype=jnp.float32):
    h, n = m["hidden"], m["n_layers"]
    k = jax.random.split(key, 2 * n + 2)
    dims = [m["embed_dim"]] + [h] * n
    return {"embed": (0.1 * jax.random.normal(k[0], (m["vocab_size"], m["embed_dim"]))
                      ).astype(dtype),
            "cells": [{"wx": _dense(k[1 + 2 * i], dims[i], 4 * h, dtype),
                       "wh": _dense(k[2 + 2 * i], h, 4 * h, dtype)} for i in range(n)],
            "head": _dense(k[-1], h, m["n_classes"], dtype)}


def logits(params, x, m, precision=None):
    seq = params["embed"][x]
    for cell in params["cells"]:
        h = c = jnp.zeros((x.shape[0], m["hidden"]), seq.dtype)
        outs = []
        for t in range(seq.shape[1]):
            z = (jnp.matmul(seq[:, t], cell["wx"]["w"], precision=precision) + cell["wx"]["b"]
                 + jnp.matmul(h, cell["wh"]["w"], precision=precision) + cell["wh"]["b"])
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            outs.append(h)
        seq = jnp.stack(outs, axis=1)
    pooled = jnp.mean(seq, axis=1)
    return jnp.matmul(pooled, params["head"]["w"], precision=precision) + params["head"]["b"]


def loss(params, batch, m, precision=None):
    """Mean cross-entropy against the one label of each sequence."""
    z = logits(params, batch["x"], m, precision)
    return jnp.mean(jax.nn.logsumexp(z, -1)
                    - jnp.take_along_axis(z, batch["y"][:, None], -1)[:, 0])


def layer_macs(m):
    h, s = m["hidden"], m["seq_len"]
    dims = [m["embed_dim"]] + [h] * m["n_layers"]
    # the embeddings are parameters: every layer needs its input gradient
    return ([(f"lstm{i + 1}", s * (dims[i] + h) * 4 * h, False) for i in range(m["n_layers"])]
            + [("head", h * m["n_classes"], False)])
