"""jit'd public wrappers: padding/layout plumbing + CPU-interpret fallback.

On a TPU backend the wrappers compile the kernels with Mosaic
(``interpret=False``); on any other backend they default to Pallas
interpret mode.
All callers go through these wrappers; tests sweep both paths' allclose
against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .spmm_blockell import spmm_blockell as _spmm_pallas
from .embedding_bag import embedding_bag as _embag_pallas
from .decode_attention import decode_attention as _decode_pallas
from .sddmm import sddmm as _sddmm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


# ------------------------------------------------------------------- spmm
def spmm(ell, x: jax.Array, interpret: bool | None = None) -> jax.Array:
    """y = A @ x from a core.blocksparse.BlockEll container."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    n, d_orig = x.shape
    xp = _pad_to(_pad_to(x, ell.bk, 0), 128, 1)
    y = _spmm_pallas(jnp.asarray(ell.block_cols),
                     jnp.asarray(ell.dense_blocks()), xp,
                     bm=ell.bm, bk=ell.bk, interpret=interpret)
    return y[:n, :d_orig]


def spmm_ref(ell, x: jax.Array) -> jax.Array:
    n, d_orig = x.shape
    xp = _pad_to(x, ell.bk, 0)
    y = ref.spmm_blockell_ref(jnp.asarray(ell.block_cols),
                              jnp.asarray(ell.dense_blocks()), xp,
                              ell.bm, ell.bk)
    return y[:n, :d_orig]


# ---------------------------------------------------------- embedding bag
def embedding_bag(ids: jax.Array, bag_ids: jax.Array, table: jax.Array,
                  num_bags: int, weights: jax.Array | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Weighted-sum EmbeddingBag.  Sorts by bag internally (kernel layout
    contract); empty bags return zeros."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    L = ids.shape[0]
    if weights is None:
        weights = jnp.ones((L,), table.dtype)
    order = jnp.argsort(bag_ids, stable=True)
    ids_s, bags_s, w_s = ids[order], bag_ids[order], weights[order]
    d_orig = table.shape[1]
    tp = _pad_to(table, 128, 1)
    out = _embag_pallas(ids_s, bags_s, w_s, tp, num_bags=num_bags,
                        interpret=interpret)
    # zero out bags that received no ids (their blocks were never initialized)
    counts = jax.ops.segment_sum(jnp.ones((L,), jnp.float32), bags_s,
                                 num_segments=num_bags)
    out = jnp.where((counts > 0)[:, None], out, 0.0)
    return out[:, :d_orig]


# --------------------------------------------------------- decode attention
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cache_len: jax.Array, bs: int = 512,
                     interpret: bool | None = None) -> jax.Array:
    """Flash-decode.  q: (B,H,d); k/v: (B,S,H,d) (H already GQA-expanded)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    S = k.shape[1]
    bs = min(bs, S)
    pad = (-S) % bs
    if pad:
        k = _pad_to(k, S + pad, 1)[:, :S + pad]
        v = _pad_to(v, S + pad, 1)[:, :S + pad]
    return _decode_pallas(q, k, v, cache_len, bs=bs, interpret=interpret)


# ------------------------------------------------------------------ sddmm
def sddmm(src: jax.Array, dst: jax.Array, q: jax.Array, k: jax.Array,
          interpret: bool | None = None) -> jax.Array:
    """Per-edge dot products (GAT edge scores).  Pads d to 128 internally."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    qp = _pad_to(q, 128, 1)
    kp = _pad_to(k, 128, 1)
    return _sddmm_pallas(src, dst, qp, kp, interpret=interpret)
