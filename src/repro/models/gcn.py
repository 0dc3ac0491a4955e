"""GCN (Kipf & Welling, arXiv:1609.02907) with Rubik-aware aggregation.

h^{l+1} = act( A_hat h^l W^l ),  A_hat = D^-1/2 (A+I) D^-1/2.

Key Rubik integration: the symmetric normalization FACTORIZES into a source
scale and a destination scale (1/sqrt(d_u) * 1/sqrt(d_v)), so the aggregation
itself runs unweighted on pre-scaled features — which is exactly what the
shared-set (G-C) computation-reuse plan requires (order-invariant, weightless
reductions).  executor in {"segment", "shared", "blockell", "fused"}:
"blockell" with a ``repro.exec.GraphExecutionPlan`` runs the aggregation as
one fused differentiable launch; "fused" goes one level further — each layer
is a ``repro.exec.LayerExecutionPlan`` call, so aggregation AND the update
matmul (+bias+ReLU) are one scheduled op with autotuned computation order.

The other executors run each layer as an aggregation and an update, in the
order ``repro.exec.plan.choose_order`` picks from the layer's static shapes:
``A_hat (h W) == (A_hat h) W``, so a shrinking layer multiplies by ``W``
first and aggregates the narrower rows.  The bias always follows the
aggregation (``A_hat``'s rows do not sum to 1).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import obs
from ..nn.layers import linear_init, linear_apply, cross_entropy
from ..core.aggregate import segment_aggregate, shared_aggregate, blockell_matmul
from ..exec.plan import choose_order


def gcn_init(key, dims: Sequence[int], param_dtype=jnp.float32) -> Dict:
    """dims = [d_in, hidden..., num_classes]."""
    keys = jax.random.split(key, len(dims) - 1)
    return {"layers": [linear_init(k, dims[i], dims[i + 1],
                                   param_dtype=param_dtype)
                       for i, k in enumerate(keys)]}


def _aggregate(x, graph, executor: str, plan=None, ell=None):
    """A_hat @ x with the chosen executor; self-loop added analytically.

    ``executor="blockell"`` with a ``repro.exec.GraphExecutionPlan`` (mode
    "gcn") runs the whole chain — source scaling, SpMM, self-loop,
    destination scaling — as ONE fused, differentiable launch; the legacy
    dict-of-arrays form keeps the old unfused jnp tile path.  Every path
    traces under the ``aggregate`` name scope (the plan's own).
    """
    if executor == "blockell" and hasattr(ell, "apply"):
        if ell.mode != "gcn":
            raise ValueError(f"plan mode {ell.mode!r} != 'gcn'; build the "
                             "plan with repro.exec.build_plan(g, 'gcn')")
        if ell.num_nodes != x.shape[0]:
            raise ValueError(f"plan compiled for {ell.num_nodes} nodes but "
                             f"x has {x.shape[0]} rows (wrong graph?)")
        return ell.apply(x)                 # fused A_hat @ x, custom VJP
    with jax.named_scope("aggregate"):
        deg = graph["deg"]                  # (N,) in-degree + 1 (self loop)
        inv_sqrt = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
        xs = x * inv_sqrt[:, None]          # source scaling
        if executor == "segment":
            agg = segment_aggregate(xs, graph["src"], graph["dst"],
                                    x.shape[0], op="sum",
                                    edge_mask=graph.get("edge_mask"))
        elif executor == "shared":
            agg = shared_aggregate(xs, plan, op="sum")
        elif executor == "blockell":
            agg = blockell_matmul(ell["block_cols"], ell["blocks"], xs,
                                  ell["bm"], ell["bk"])
        else:
            raise ValueError(executor)
        agg = agg + xs                      # self loop
        return agg * inv_sqrt[:, None]      # destination scaling


def _layer_plans_for(ell, params, mode: str):
    """Validate a per-layer ``repro.exec.LayerExecutionPlan`` sequence (a
    ``repro.exec.ForwardExecutionPlan`` unwraps to its scheduled layers)."""
    layers = params["layers"]
    if hasattr(ell, "layers") and hasattr(ell, "configs"):
        ell = ell.layers                    # ForwardExecutionPlan
    plans = list(ell) if isinstance(ell, (list, tuple)) else None
    if plans is None or len(plans) != len(layers) or not all(
            hasattr(lp, "apply") and hasattr(lp, "order") for lp in plans):
        raise ValueError(
            "executor='fused' needs one repro.exec.LayerExecutionPlan per "
            f"layer ({len(layers)} layers; got {type(ell).__name__})")
    for lp in plans:
        if lp.mode != mode:
            raise ValueError(f"layer plan mode {lp.mode!r} != {mode!r}; "
                             f"build with repro.exec.build_layer_plan(g, "
                             f"{mode!r}, ...)")
    return plans


def gcn_apply(params, x: jax.Array, graph: Dict[str, Any],
              executor: str = "segment", plan=None, ell=None,
              act=jax.nn.relu) -> jax.Array:
    """Layer ``i`` traces under the name scope ``layer{i}``: its
    aggregation under ``aggregate``, its weight product, bias and activation
    under ``update`` (a fused layer kernel, which does both in one launch,
    under ``aggregate``).  Backward ops inherit the scopes.  Outside
    ``executor="fused"`` each layer's order is ``choose_order``'s verdict on
    its shapes, counted as ``model.gcn.order`` once per trace."""
    h = x
    n_layers = len(params["layers"])
    if executor == "fused":
        # hierarchical fusion: each layer (aggregate + update + bias + ReLU)
        # is ONE LayerExecutionPlan call with autotuned computation order
        plans = _layer_plans_for(ell, params, "gcn")
        if act is not jax.nn.relu:
            # the layer kernels only fuse ReLU: run each layer through its
            # graph plan (fused aggregation, unfused update + act) instead
            import warnings
            warnings.warn("executor='fused' layer plans only fuse ReLU; "
                          "falling back to the per-layer graph-plan path "
                          "for this activation", stacklevel=2)
            for i, (p, lp) in enumerate(zip(params["layers"], plans)):
                with jax.named_scope(f"layer{i}"):
                    agg = lp.gplan.apply(h)
                    with jax.named_scope("update"):
                        h = linear_apply(p, agg)
                        if i + 1 < n_layers:
                            h = act(h)
            return h
        for i, (p, lp) in enumerate(zip(params["layers"], plans)):
            with jax.named_scope(f"layer{i}"):
                h = lp.apply(h, p["w"], p.get("b"), relu=i + 1 < n_layers)
        return h
    for i, p in enumerate(params["layers"]):
        order = choose_order(h.shape[0], graph["src"].shape[0], *p["w"].shape)
        obs.counter("model.gcn.order", order=order).inc()
        with jax.named_scope(f"layer{i}"):
            if order == "update_first":
                with jax.named_scope("update"):
                    h = h @ p["w"].astype(h.dtype)
            h = _aggregate(h, graph, executor, plan, ell)
            with jax.named_scope("update"):
                if order == "aggregate_first":
                    h = linear_apply(p, h)
                elif "b" in p:
                    h = h + p["b"].astype(h.dtype)
                if i + 1 < n_layers:
                    h = act(h)
    return h


def gcn_loss(params, x, graph, labels, mask, executor="segment",
             plan=None, ell=None):
    logits = gcn_apply(params, x, graph, executor, plan, ell)
    return cross_entropy(logits, labels, mask.astype(jnp.float32))


def make_graph_inputs(g, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Device-ready graph dict from a numpy Graph (adds self-loop degrees)."""
    import numpy as np
    deg = g.in_degrees().astype(np.float32) + 1.0
    out = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst),
           "deg": jnp.asarray(deg)}
    if g.edge_mask is not None:
        out["edge_mask"] = jnp.asarray(g.edge_mask)
    return out
