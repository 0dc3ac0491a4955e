"""Mesh-level flash decode: KV cache sequence-sharded on the model axis.

The per-device kernel (kernels/decode_attention.py) keeps a running
(max, denominator, accumulator) across KV blocks; this module runs the SAME
recurrence one level up: each model shard reduces its local KV slice to a
partial (m, l, acc) triple, then one pmax + two psums merge the partials —
the LSE-merge the kernel docstring promises.  Batch rides the data axis
untouched.  Per-chip collective payload is O(B*H*d), independent of S; the
naive alternative (all-gather K and V) is O(B*S*H*d/shards).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def distributed_decode_attention(mesh: Mesh, q: jax.Array, k: jax.Array,
                                 v: jax.Array, cache_lens: jax.Array,
                                 data_axis: str = "data",
                                 model_axis: str = "model",
                                 scale: Optional[float] = None) -> jax.Array:
    """q: (B, H, d); k/v: (B, S, H, d); cache_lens: (B,) valid KV lengths.

    Matches ``kernels.ref.decode_attention_ref`` with B sharded over
    ``data_axis`` and S sharded over ``model_axis``.  Requires B and S
    divisible by the respective axis sizes (static shapes under shard_map).
    """
    B, S = k.shape[0], k.shape[1]
    d = q.shape[-1]
    for dim, axis in ((B, data_axis), (S, model_axis)):
        if dim % mesh.shape[axis] != 0:
            raise ValueError(f"dim {dim} not divisible by mesh axis "
                             f"'{axis}' ({mesh.shape[axis]})")
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    def body(ql, kl, vl, lens):
        Sl = kl.shape[1]
        off = jax.lax.axis_index(model_axis) * Sl
        scores = jnp.einsum("bhd,bshd->bhs", ql, kl).astype(jnp.float32) * sc
        pos = off + jnp.arange(Sl)
        valid = pos[None, :] < lens[:, None]                  # (Bl, Sl)
        scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
        # local partials; a shard whose whole slice is masked keeps m = -inf
        m = jnp.max(scores, axis=-1)                          # (Bl, H)
        m_glob = jax.lax.pmax(m, model_axis)
        p = jnp.where(jnp.isfinite(scores),
                      jnp.exp(scores - m_glob[..., None]), 0.0)
        l = jax.lax.psum(jnp.sum(p, axis=-1), model_axis)     # (Bl, H)
        acc = jax.lax.psum(
            jnp.einsum("bhs,bshd->bhd", p.astype(vl.dtype), vl
                       ).astype(jnp.float32), model_axis)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(ql.dtype)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axis, None, None),
                  P(data_axis, model_axis, None, None),
                  P(data_axis, model_axis, None, None),
                  P(data_axis)),
        out_specs=P(data_axis, None, None))
    return fn(q, k, v, cache_lens)
