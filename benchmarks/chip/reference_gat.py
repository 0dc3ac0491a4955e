"""The plain reference for GAT: a float32 GAT in ``jax.numpy``, with Adam.

GAT after Velickovic et al. (arXiv:1710.10903), as DGL's ogbn-arxiv
example configures it.  Layer ``l`` with ``H`` heads of ``F`` features over
the edge list with one self-loop edge ``i -> i`` appended per node::

    z^h     = h W[:, hF:(h+1)F]
    e_ij^h  = LeakyReLU_0.2(z_j^h a_src^h + z_i^h a_dst^h)     (j -> i)
    alpha^h = exp(e^h - max) / sum over i's in-edges of the same
    m_i^h   = d_in(i)^+1/2 sum_j alpha_ij^h d_out(j)^-1/2 z_j^h
    out^h   = m^h + (h R)^h

Degrees count the self loop.  Hidden layers give ``ReLU(concat_h out^h)``,
the last ``mean_h out^h + b``.  The loss is the mean cross entropy over the
training nodes; the optimizer is Adam after clipping the gradient's global
norm, with ``reference.py``'s constants.  The softmax is
``jax.ops.segment_max`` and ``segment_sum`` over explicit edges, the sum a
gather and a ``segment_sum``; nothing here imports the program under test.

Every matrix product, the score dot-products included, goes through one
``matmul`` argument: ``reference.matmul_highest``, or
``reference.matmul_bf16x3`` as the control.  Its one departure from the
plainest form: each head runs under ``jax.checkpoint``, so that the
backward pass gathers a head's ``(E, F)`` rows again rather than keeping
them, which at ogbn-arxiv's size is what fits one chip.

Parameters: one dict per layer, ``{"a_dst", "a_src", "res", "w"}`` (``w``
and ``res`` of shape ``(d_in, H * F)``, ``a_*`` of ``(H, F)``), the last
also ``"b"``.  :func:`init` draws them from a run's seed: ``w`` and
``res`` ``N(0, 1/d_in)``, ``a_*`` ``N(0, 0.01)``, ``b`` zero, the
distribution of the program's GAT, drawn here and not by the program.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import functools
import math

import jax
import jax.numpy as jnp

import reference

NEGATIVE_SLOPE = 0.2


@functools.partial(jax.jit, static_argnames=("d_in", "heads", "widths"))
def init(key, d_in: int, heads: Sequence[int], widths: Sequence[int]):
    """The weights of a GAT whose layer ``l`` has ``heads[l]`` heads of
    ``widths[l]`` features; hidden layers concatenate their heads."""
    layers = []
    for i, (k, h, f) in enumerate(zip(jax.random.split(key, len(heads)),
                                      heads, widths)):
        k_w, k_r, k_s, k_d = jax.random.split(k, 4)
        scale = 1.0 / math.sqrt(d_in)
        layer = {"a_dst": 0.1 * jax.random.normal(k_d, (h, f), jnp.float32),
                 "a_src": 0.1 * jax.random.normal(k_s, (h, f), jnp.float32),
                 "res": scale * jax.random.normal(k_r, (d_in, h * f),
                                                  jnp.float32),
                 "w": scale * jax.random.normal(k_w, (d_in, h * f),
                                                jnp.float32)}
        if i + 1 == len(heads):
            layer["b"] = jnp.zeros((f,), jnp.float32)
        layers.append(layer)
        d_in = h * f
    return layers


def with_self_loops(src, dst, n: int):
    """The edge list with one edge ``i -> i`` appended per node."""
    loops = jnp.arange(n, dtype=src.dtype)
    return jnp.concatenate([src, loops]), jnp.concatenate([dst, loops])


def _head(zh, a_src, a_dst, src, dst, s_out, s_in, matmul: Callable):
    """One head's ``m`` over the edges with self loops: (N, F)."""
    n = zh.shape[0]
    s = matmul(zh, a_src[:, None])[:, 0]
    t = matmul(zh, a_dst[:, None])[:, 0]
    e = jax.nn.leaky_relu(s[src] + t[dst], NEGATIVE_SLOPE)
    mx = jax.ops.segment_max(e, dst, num_segments=n)
    ex = jnp.exp(e - mx[dst])
    alpha = ex / jax.ops.segment_sum(ex, dst, num_segments=n)[dst]
    msgs = alpha[:, None] * (zh * s_out[:, None])[src]
    return jax.ops.segment_sum(msgs, dst, num_segments=n) * s_in[:, None]


def forward(params: Sequence[Dict], x, src, dst, matmul: Callable):
    """Logits of the GAT; ``src``/``dst`` without self loops."""
    n = x.shape[0]
    src, dst = with_self_loops(src, dst, n)
    ones = jnp.ones(src.shape[0], jnp.float32)
    s_in = jnp.sqrt(jax.ops.segment_sum(ones, dst, num_segments=n))
    s_out = 1.0 / jnp.sqrt(jax.ops.segment_sum(ones, src, num_segments=n))
    head = jax.checkpoint(_head, static_argnums=(7,))
    h = x
    for i, p in enumerate(params):
        heads, f = p["a_src"].shape
        z = matmul(h, p["w"])
        res = matmul(h, p["res"])
        out = [head(z[:, k * f:(k + 1) * f], p["a_src"][k], p["a_dst"][k],
                    src, dst, s_out, s_in, matmul)
               + res[:, k * f:(k + 1) * f] for k in range(heads)]
        if i + 1 < len(params):
            h = jnp.maximum(jnp.concatenate(out, axis=1), 0.0)
        else:
            h = sum(out) / heads + p["b"]
    return h


def loss(params, x, src, dst, labels, mask, matmul: Callable):
    logits = forward(params, x, src, dst, matmul)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    w = mask.astype(jnp.float32)
    return jnp.sum((logz - gold) * w) / jnp.sum(w)


_loss_and_grad = jax.jit(jax.value_and_grad(loss),
                         static_argnames=("matmul",))


def train_steps(params: List[Dict], x, src, dst, labels, mask, *,
                steps: int, lr: float, clip_norm: float, matmul: Callable):
    """``steps`` steps of Adam from ``params``.  Returns the loss of each
    step, the first step's clipped gradient, and the final parameters."""
    b1, b2, eps = reference.ADAM_B1, reference.ADAM_B2, reference.ADAM_EPS
    tmap = jax.tree_util.tree_map
    m = tmap(jnp.zeros_like, params)
    v = tmap(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t in range(1, steps + 1):
        val, g = _loss_and_grad(params, x, src, dst, labels, mask,
                                matmul=matmul)
        leaves = jax.tree_util.tree_leaves(g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in leaves))
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-9))
        g = tmap(lambda a: a * scale, g)
        if first_grad is None:
            first_grad = jax.device_get(g)
        m = tmap(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = tmap(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        # bias corrections in float32, as float32 Adam computes them
        step = jnp.float32(t)
        bc1 = 1 - jnp.float32(b1) ** step
        bc2 = 1 - jnp.float32(b2) ** step
        params = tmap(lambda p, a, b: p - lr * (a / bc1)
                      / (jnp.sqrt(b / bc2) + eps), params, m, v)
        losses.append(float(val))
    return losses, first_grad, jax.device_get(params)
