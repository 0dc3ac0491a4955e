"""The plain reference: a float32 GCN in ``jax.numpy``, with Adam.

GCN after Kipf & Welling (arXiv:1609.02907): ``H' = act(A_hat H W + b)``
with ``A_hat = D^-1/2 (A + I) D^-1/2`` and ``D`` the in-degree plus the self
loop.  The aggregation is a gather over the edge list and a segment sum
(``jax.ops.segment_sum``); the loss is the mean cross entropy over the
training nodes; the optimizer is Adam after clipping the gradient's global
norm.  Nothing here imports the program under test.

Every matrix product goes through one ``matmul`` argument:

* :func:`matmul_highest`: float32 products at ``Precision.HIGHEST``, the
  precision the configurations state;
* :func:`matmul_bf16x3`: the control, one step of precision below: each
  operand split into a bfloat16 head and a bfloat16 tail, and the three
  products that are not tail times tail summed in float32.  This is the
  arithmetic of the TPU's ``Precision.HIGH``, written out so that it means
  the same on every backend; its gradients use the same three products.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def matmul_highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32.  ``reduce_precision``
    and not a round trip through ``astype``: XLA may drop such a round trip
    as excess precision, which would leave the tail at zero."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi.astype(jnp.bfloat16), _bf16(a - hi).astype(jnp.bfloat16)


def _mm3(a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)

    def dot(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


@jax.custom_vjp
def matmul_bf16x3(a, b):
    return _mm3(a, b)


def _mm3_fwd(a, b):
    return _mm3(a, b), (a, b)


def _mm3_bwd(res, g):
    a, b = res
    return _mm3(g, b.T), _mm3(a.T, g)


matmul_bf16x3.defvjp(_mm3_fwd, _mm3_bwd)

def forward(params: Sequence[Dict], x, src, dst, matmul: Callable):
    """Logits of the GCN; ``params`` is a list of ``{"w", "b"}``."""
    n = x.shape[0]
    deg = jax.ops.segment_sum(jnp.ones_like(dst, jnp.float32), dst,
                              num_segments=n) + 1.0
    s = 1.0 / jnp.sqrt(deg)
    h = x
    for i, p in enumerate(params):
        hs = h * s[:, None]
        agg = (jax.ops.segment_sum(hs[src], dst, num_segments=n) + hs)
        h = matmul(agg * s[:, None], p["w"]) + p["b"]
        if i + 1 < len(params):
            h = jnp.maximum(h, 0.0)
    return h


def loss(params, x, src, dst, labels, mask, matmul: Callable):
    logits = forward(params, x, src, dst, matmul)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    w = mask.astype(jnp.float32)
    return jnp.sum((logz - gold) * w) / jnp.sum(w)


forward_jit = jax.jit(forward, static_argnames=("matmul",))
_loss_and_grad = jax.jit(jax.value_and_grad(loss),
                         static_argnames=("matmul",))


def train_steps(params: List[Dict], x, src, dst, labels, mask, *,
                steps: int, lr: float, clip_norm: float, matmul: Callable):
    """``steps`` steps of Adam from ``params``.  Returns the loss of each
    step, the first step's clipped gradient, and the final parameters."""
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t in range(1, steps + 1):
        val, g = _loss_and_grad(params, x, src, dst, labels, mask,
                                matmul=matmul)
        leaves = jax.tree_util.tree_leaves(g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in leaves))
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-9))
        g = jax.tree_util.tree_map(lambda a: a * scale, g)
        if first_grad is None:
            first_grad = jax.device_get(g)
        m = jax.tree_util.tree_map(
            lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = jax.tree_util.tree_map(
            lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
        # bias corrections in float32, as float32 Adam computes them
        step = jnp.float32(t)
        bc1 = 1 - jnp.float32(ADAM_B1) ** step
        bc2 = 1 - jnp.float32(ADAM_B2) ** step
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * (a / bc1) / (jnp.sqrt(b / bc2)
                                                  + ADAM_EPS),
            params, m, v)
        losses.append(float(val))
    return losses, first_grad, jax.device_get(params)

