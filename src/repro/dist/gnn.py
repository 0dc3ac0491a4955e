"""Sharded GCN/SAGE training: aggregation routed through the halo exchange.

The first end-to-end multi-device path in the repo: node features, edges, and
the aggregation all live sharded in contiguous windows (the paper's
graph-level mapping with mesh shards as PEs), every layer's neighborhood sum
runs through ``halo_aggregate``, and the backward pass differentiates through
the all_to_all.  Parameters stay replicated (they are tiny next to features);
gradients reduce via the stock psum that jit inserts.

Usage (CPU debug mesh):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch gcn-cora --dist
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..graph.partition import HaloPlan, build_halo_plan
from ..graph.structure import Graph
from ..train.optimizer import adam, apply_updates, clip_by_global_norm
from .halo import halo_aggregate, allgather_aggregate
from .plan import SendPlan, build_send_plan, collective_bytes_estimate


# ---------------------------------------------------------------- graph prep
def pad_graph_nodes(g: Graph, multiple: int) -> Graph:
    """Append isolated padding nodes so num_nodes divides ``multiple``.

    Padding nodes have zero features, label 0, and train_mask False, so they
    never contribute to the loss; they receive no edges, so aggregation over
    them is zero.  Required because the window partition hands every mesh
    shard an identical static node count.
    """
    n = g.num_nodes
    target = int(math.ceil(n / multiple) * multiple)
    if target == n:
        return g
    pad = target - n

    def pad_rows(a, fill=0):
        if a is None:
            return None
        shape = (pad,) + a.shape[1:]
        return np.concatenate([a, np.full(shape, fill, a.dtype)])

    return dataclasses.replace(
        g, num_nodes=target,
        node_feat=pad_rows(g.node_feat, 0),
        labels=pad_rows(g.labels, 0),
        train_mask=pad_rows(g.train_mask, False))


# ------------------------------------------------------------------- model
def dist_gnn_init(key, dims: List[int]) -> List[Dict[str, jax.Array]]:
    """SAGE-style layers: h' = h W_self + AGG(h) W_neigh + b."""
    params = []
    for din, dout in zip(dims[:-1], dims[1:]):
        key, k1, k2 = jax.random.split(key, 3)
        s = 1.0 / math.sqrt(din)
        params.append({
            "w_self": jax.random.normal(k1, (din, dout)) * s,
            "w_neigh": jax.random.normal(k2, (din, dout)) * s,
            "b": jnp.zeros((dout,)),
        })
    return params


def dist_gnn_apply(mesh, params, x: jax.Array, plan: HaloPlan,
                   send: SendPlan, local_n: int,
                   deg: Optional[jax.Array] = None,
                   aggregator: str = "halo") -> jax.Array:
    """Forward pass with sharded aggregation.

    ``deg`` (N,) switches the neighborhood sum to a mean (GraphSAGE-mean);
    None keeps the raw (edge-weighted) sum, which is exact GCN when the
    plan's edge weights carry the symmetric normalization.
    ``aggregator`` selects the collective: "halo", the "allgather" baseline,
    or "resilient" (halo with per-step fallback to allgather on shard
    loss/straggler — :mod:`repro.dist.resilient`).
    """
    if aggregator == "resilient":
        from .resilient import resilient_halo_aggregate as agg_fn
    else:
        agg_fn = (halo_aggregate if aggregator == "halo"
                  else allgather_aggregate)
    h = x
    for i, lp in enumerate(params):
        a = (agg_fn(mesh, h, plan, send, local_n)
             if aggregator in ("halo", "resilient")
             else agg_fn(mesh, h, plan, local_n))
        if deg is not None:
            a = a / jnp.maximum(deg, 1.0)[:, None]
        h = h @ lp["w_self"] + a @ lp["w_neigh"] + lp["b"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


def dist_gnn_loss(mesh, params, batch, plan, send, local_n,
                  aggregator: str = "halo") -> jax.Array:
    """Masked softmax cross-entropy over training nodes."""
    logits = dist_gnn_apply(mesh, params, batch["x"], plan, send, local_n,
                            deg=batch.get("deg"), aggregator=aggregator)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch["labels"][:, None], axis=1)[:, 0]
    mask = batch["train_mask"].astype(jnp.float32)
    return -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def make_dist_train_step(mesh, plan, send, local_n, opt,
                         aggregator: str = "halo"):
    """jit-compiled (params, opt_state, batch) -> (params, opt_state, loss)."""

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: dist_gnn_loss(mesh, p, batch, plan, send, local_n,
                                    aggregator))(params)
        grads, _ = clip_by_global_norm(grads, 1.0)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state2, loss

    return jax.jit(step, donate_argnums=(0, 1))


# ------------------------------------------------------------------ driver
def train_distributed(arch: str = "gcn-cora", steps: int = 20,
                      parts: Optional[int] = None, lr: float = 1e-2,
                      hidden: int = 64, aggregator: str = "halo",
                      ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                      log=print) -> Dict:
    """End-to-end sharded GNN training on whatever devices exist.

    Builds the LSH-reordered halo plan over ``parts`` contiguous windows
    (default: one per device), then trains with every aggregation running
    through the mesh exchange.  Returns losses plus the collective-bytes
    estimate so callers can report the halo-vs-allgather headroom.

    ``ckpt_dir`` enables **buddy-mirrored** checkpoints
    (:func:`repro.train.checkpoint.save_mirrored_checkpoint`, one slice per
    logical shard plus its neighbour's mirror) every ``ckpt_every`` steps —
    the restore side needs only a quorum of one copy per slice, so losing a
    whole shard's directory is survivable.

    Only the GCN/SAGE-style archs map onto the dist layer today (the layer
    is ``h W_self + AGG(h) W_neigh``); attention/equivariant GNNs need
    their own sharded message functions.
    """
    from ..graph.datasets import cora_like
    from ..core.reorder import minhash_reorder
    from ..launch.mesh import make_halo_debug_mesh

    if arch not in ("gcn-cora", "graphsage", "sage"):
        raise ValueError(
            f"--dist currently trains the sharded GCN/SAGE layer only; "
            f"'{arch}' has no distributed message function yet")

    parts = parts or jax.device_count()
    mesh = make_halo_debug_mesh(parts)
    g = cora_like()
    g = g.permute(minhash_reorder(g))
    g = pad_graph_nodes(g, parts)
    local_n = g.num_nodes // parts
    plan = build_halo_plan(g, parts)
    send = build_send_plan(plan)
    est = collective_bytes_estimate(plan, send, d=g.node_feat.shape[1])
    log(f"dist[{arch}] parts={parts} cut={est['cut_edge_fraction']:.3f} "
        f"halo={est['halo_bytes_per_chip_real'] / 1e3:.1f}kB/chip "
        f"vs allgather={est['allgather_bytes_per_chip'] / 1e3:.1f}kB/chip")

    n_classes = int(g.labels.max()) + 1
    deg = g.in_degrees().astype(np.float32)
    # node-axis arrays go straight from the host to their shards (never
    # whole onto one device); the small parameters are replicated
    batch = jax.device_put({"x": g.node_feat,
                            "labels": g.labels.astype(np.int32),
                            "train_mask": g.train_mask, "deg": deg},
                           NamedSharding(mesh, P("data")))
    params = jax.device_put(
        dist_gnn_init(jax.random.PRNGKey(0),
                      [g.node_feat.shape[1], hidden, n_classes]),
        NamedSharding(mesh, P()))
    opt = adam(lr)
    opt_state = opt.init(params)
    obs.gauge("dist.parts").set(parts)
    with mesh:
        step = make_dist_train_step(mesh, plan, send, local_n, opt,
                                    aggregator)
        losses = []
        step_hist = obs.histogram("dist.step_seconds")
        for i in range(steps):
            with obs.span("dist.step", cat="dist", aggregator=aggregator):
                t0 = time.perf_counter()
                params, opt_state, loss = step(params, opt_state, batch)
                losses.append(float(loss))
            step_hist.observe(time.perf_counter() - t0)
            if ckpt_dir and ckpt_every and (i + 1) % ckpt_every == 0:
                from ..train.checkpoint import save_mirrored_checkpoint
                save_mirrored_checkpoint(ckpt_dir, i + 1, params, opt_state,
                                         num_shards=parts)
        obs.counter("dist.steps").inc(steps)
    log(f"dist[{arch}]: {steps} steps, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}")
    return {"losses": losses, "collective_estimate": est, "params": params,
            "batch": batch}
