"""Features and weights of a run, made on the device from its seed.

One jitted call draws both: class-informative features (a random centre
per class, half weight, over unit noise, as the graph generator draws
them) and GCN weights ``N(0, 1/d_in)`` with zero biases, the program's
``nn.layers.linear_init`` distribution.  The weights come in the layout
the program takes, ``{"layers": [{"w", "b"}, ...]}``.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("dims",))
def make(key, labels, dims: Sequence[int]):
    """``labels`` (N,) int32 in the node order the features are wanted in;
    ``dims`` = (d_in, hidden..., classes).  Returns ``(x, params)``."""
    k_x, k_c, k_w = jax.random.split(key, 3)
    d0, classes = dims[0], dims[-1]
    centers = jax.random.normal(k_c, (classes, d0), jnp.float32)
    x = (jax.random.normal(k_x, (labels.shape[0], d0), jnp.float32)
         + 0.5 * centers[labels % classes])
    layers = []
    for k, (a, b) in zip(jax.random.split(k_w, len(dims) - 1),
                         zip(dims[:-1], dims[1:])):
        layers.append({"w": jax.random.normal(k, (a, b), jnp.float32)
                       / math.sqrt(a),
                       "b": jnp.zeros((b,), jnp.float32)})
    return x, {"layers": layers}


def as_reference(params) -> list:
    """The reference's layout: a list of ``{"w", "b"}``."""
    return [dict(p) for p in params["layers"]]
