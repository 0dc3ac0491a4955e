"""Sharded graph aggregation: halo exchange vs. the all-gather baseline.

Both entry points compute exactly ``core.segment_aggregate`` (weighted-sum
semantics over the plan's edge lists) with the node axis sharded over one
mesh axis — they are drop-in replacements for each other and for the
single-device oracle, differing only in collective volume:

* ``halo_aggregate``      — one tiled ``all_to_all`` moving only the
  deduplicated cut-edge rows (SendPlan tables), then a purely local
  gather + segment-sum over the renumbered [owned | halo] row space.
* ``allgather_aggregate`` — ships the full feature table (``all_gather``)
  and reads halo rows out of it; the GSPMD-auto baseline made explicit.

Both are differentiable (all_to_all/all_gather transpose to themselves /
reduce-scatter), so the sharded GNN train step in dist/gnn.py backprops
straight through the exchange.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..graph.partition import HaloPlan, uniform_local_n
from .plan import SendPlan


def _check_local_n(plan: HaloPlan, local_n: int) -> None:
    if uniform_local_n(plan.parts) != local_n:
        raise ValueError(
            f"caller claims local_n={local_n} but the plan's windows hold "
            f"{uniform_local_n(plan.parts)} nodes each")


def _resolve_axis(mesh: Mesh, axis_name: Optional[str], num_parts: int) -> str:
    axis_name = axis_name or mesh.axis_names[0]
    if mesh.shape[axis_name] != num_parts:
        raise ValueError(
            f"plan has {num_parts} parts but mesh axis '{axis_name}' has "
            f"size {mesh.shape[axis_name]}")
    return axis_name


def halo_aggregate(mesh: Mesh, x: jax.Array, plan: HaloPlan, send: SendPlan,
                   local_n: int, axis_name: Optional[str] = None) -> jax.Array:
    """Sharded ``a[v] = sum_{(u->v)} w_uv * x[u]`` via halo exchange.

    x: (N, d) node features, sharded (or shardable) over ``axis_name`` in
    contiguous windows matching ``plan.parts``.  Returns (N, d) aggregated
    features with the same layout.
    """
    axis = _resolve_axis(mesh, axis_name, plan.parts.num_parts)
    _check_local_n(plan, local_n)
    H = plan.halo_capacity
    tables = (jnp.asarray(send.send_idx), jnp.asarray(send.send_mask),
              jnp.asarray(send.recv_slot), jnp.asarray(send.recv_mask),
              jnp.asarray(plan.edge_src), jnp.asarray(plan.edge_dst),
              jnp.asarray(plan.edge_weight))

    def body(xl, si, sm, rs, rm, es, ed, ew):
        # tables arrive with a leading shard dim of 1
        si, sm, rs, rm = si[0], sm[0], rs[0], rm[0]     # (P, K)
        rows = jnp.where(sm[:, :, None], xl[si], 0.0)   # (P, K, d)
        got = jax.lax.all_to_all(rows, axis, split_axis=0, concat_axis=0,
                                 tiled=True)            # got[q] = from part q
        slot = jnp.where(rm, rs, H - 1).reshape(-1)
        vals = jnp.where(rm[:, :, None], got, 0.0).reshape(-1, xl.shape[1])
        halo = jnp.zeros((H, xl.shape[1]), xl.dtype).at[slot].add(vals)
        full = jnp.concatenate([xl, halo], axis=0)      # [owned | halo] rows
        msgs = full[es[0]] * ew[0][:, None]             # padding has w = 0
        return jax.ops.segment_sum(msgs, ed[0], num_segments=local_n)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis, None),) + (P(axis),) * 7,
                       out_specs=P(axis, None))
    return fn(x, *tables)


def allgather_aggregate(mesh: Mesh, x: jax.Array, plan: HaloPlan,
                        local_n: int, axis_name: Optional[str] = None,
                        send: Optional[SendPlan] = None) -> jax.Array:
    """Same result as ``halo_aggregate`` but shipping the FULL feature table.

    ``send`` is accepted (and ignored) so callers can flip between the two
    executors without changing the call site.
    """
    axis = _resolve_axis(mesh, axis_name, plan.parts.num_parts)
    _check_local_n(plan, local_n)
    tables = (jnp.asarray(plan.halo_src), jnp.asarray(plan.halo_mask),
              jnp.asarray(plan.edge_src), jnp.asarray(plan.edge_dst),
              jnp.asarray(plan.edge_weight))

    def body(xl, hs, hm, es, ed, ew):
        xg = jax.lax.all_gather(xl, axis, axis=0, tiled=True)   # (N, d)
        halo = jnp.where(hm[0][:, None], xg[hs[0]], 0.0)        # (H, d)
        full = jnp.concatenate([xl, halo], axis=0)
        msgs = full[es[0]] * ew[0][:, None]
        return jax.ops.segment_sum(msgs, ed[0], num_segments=local_n)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis, None),) + (P(axis),) * 5,
                       out_specs=P(axis, None))
    return fn(x, *tables)
