"""Aggregation executors: the graph-level computing engine (paper's C1).

Interchangeable execution strategies for `a_v = AGG_{u in N(v)} x_u`:

* ``segment_aggregate``   — canonical JAX path (gather + segment reduce);
                            the "index-order" reference executor.
* ``shared_aggregate``    — G-C computation-reuse executor driven by a
                            ``SharedSetPlan`` (paper §IV-A2): shared-set
                            partials built once, consumed by every buddy
                            destination (levels>1 = hierarchical extension).
* ``blockell_matmul``     — block-ELL dense-tile executor (jnp fallback for
                            the Pallas kernel in kernels/spmm_blockell.py).

All are pure JAX and differentiable; all agree with each other (tests).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .shared_set import SharedSetPlan


# --------------------------------------------------------------------------
# canonical segment-reduce executor
# --------------------------------------------------------------------------
def segment_aggregate(x: jax.Array, src: jax.Array, dst: jax.Array,
                      num_nodes: int, op: str = "sum",
                      edge_weight: Optional[jax.Array] = None,
                      edge_mask: Optional[jax.Array] = None) -> jax.Array:
    """a[v] = op_{(u->v)} (w_uv * x[u]).  op in {sum, mean, max, min};
    ``edge_weight`` is (E,), or (E, d) for a weight per feature."""
    msgs = x[src]
    if edge_weight is not None:
        msgs = msgs * (edge_weight if edge_weight.ndim == 2
                       else edge_weight[:, None])
    if edge_mask is not None:
        if op in ("sum", "mean"):
            msgs = jnp.where(edge_mask[:, None], msgs, 0.0)
        elif op == "max":
            msgs = jnp.where(edge_mask[:, None], msgs, -jnp.inf)
        elif op == "min":
            msgs = jnp.where(edge_mask[:, None], msgs, jnp.inf)
    if op in ("sum", "mean"):
        out = jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)
        if op == "mean":
            ones = (edge_mask.astype(x.dtype) if edge_mask is not None
                    else jnp.ones(src.shape[0], x.dtype))
            deg = jax.ops.segment_sum(ones, dst, num_segments=num_nodes)
            out = out / jnp.maximum(deg, 1.0)[:, None]
        return out
    if op == "max":
        out = jax.ops.segment_max(msgs, dst, num_segments=num_nodes)
        return jnp.where(jnp.isfinite(out), out, 0.0)
    if op == "min":
        out = jax.ops.segment_min(msgs, dst, num_segments=num_nodes)
        return jnp.where(jnp.isfinite(out), out, 0.0)
    raise ValueError(op)


# --------------------------------------------------------------------------
# G-C shared-set executor (paper CR; levels>1 = hierarchical extension)
# --------------------------------------------------------------------------
def shared_aggregate(x: jax.Array, plan: SharedSetPlan, op: str = "sum"
                     ) -> jax.Array:
    """Two-phase aggregation with shared-set computation reuse.

    SA_l[b] aggregates the sources shared by the whole destination block b of
    size 2^(l+1); every original edge lives in exactly one list so summing
    residual + all consumed levels reconstructs each row exactly.
    """
    if op not in ("sum", "mean", "max", "min"):
        raise ValueError(op)
    N = plan.num_nodes
    is_minmax = op in ("max", "min")
    seg = {"sum": jax.ops.segment_sum, "mean": jax.ops.segment_sum,
           "max": jax.ops.segment_max, "min": jax.ops.segment_min}[op]
    comb = {"max": jnp.maximum, "min": jnp.minimum}.get(op)

    rs = jnp.asarray(plan.residual_src)
    rd = jnp.asarray(plan.residual_dst)
    out = seg(x[rs], rd, num_segments=N)
    if op == "mean":
        deg = jax.ops.segment_sum(jnp.ones(rs.shape[0], x.dtype), rd,
                                  num_segments=N)
    for l in range(plan.num_levels):
        if plan.level_src[l].shape[0] == 0:
            continue
        width = 2 ** (l + 1)
        nb = (N + width - 1) // width
        s = jnp.asarray(plan.level_src[l])
        b = jnp.asarray(plan.level_block[l])
        sa = seg(x[s], b, num_segments=nb)          # (nb, d) shared partials
        spread = jnp.repeat(sa, width, axis=0)[:N]  # consume: SA[d >> (l+1)]
        if is_minmax:
            out = comb(out, spread)
        else:
            out = out + jnp.where(jnp.isfinite(spread), spread, 0.0)
        if op == "mean":
            cnt = jax.ops.segment_sum(jnp.ones(s.shape[0], x.dtype), b,
                                      num_segments=nb)
            deg = deg + jnp.repeat(cnt, width, axis=0)[:N]
    if is_minmax:
        return jnp.where(jnp.isfinite(out), out, 0.0)
    if op == "mean":
        out = out / jnp.maximum(deg, 1.0)[:, None]
    return out


# --------------------------------------------------------------------------
# block-ELL executor (jnp fallback of the Pallas kernel)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("bm", "bk"))
def blockell_matmul(block_cols: jax.Array, blocks: jax.Array, x: jax.Array,
                    bm: int, bk: int) -> jax.Array:
    """y = A @ x with A in block-ELL.  Grid loops over (row_block, slot).

    Inactive slots (col == -1) multiply a zero tile — numerically exact and
    branch-free; the Pallas version predicated-skips them instead.
    """
    R, W = block_cols.shape
    n = x.shape[0]
    C = -(-n // bk)
    xp = jnp.pad(x, ((0, C * bk - n), (0, 0)))
    xb = xp.reshape(C, bk, x.shape[1])

    def row(rb_cols, rb_blocks):
        safe = jnp.maximum(rb_cols, 0)
        tiles = xb[safe]                                   # (W, bk, d)
        tiles = jnp.where((rb_cols >= 0)[:, None, None], tiles, 0.0)
        # (W, bm, bk) @ (W, bk, d) summed over W
        return jnp.einsum("wmk,wkd->md", rb_blocks, tiles)

    y = jax.vmap(row)(block_cols, blocks)                  # (R, bm, d)
    return y.reshape(R * bm, x.shape[1])[:n]


def blockell_aggregate(ell, x: jax.Array) -> jax.Array:
    """Convenience wrapper over numpy BlockEll containers."""
    return blockell_matmul(jnp.asarray(ell.block_cols),
                           jnp.asarray(ell.dense_blocks()),
                           x, ell.bm, ell.bk)
