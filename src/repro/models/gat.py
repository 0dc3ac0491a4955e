"""GAT (Velickovic et al., arXiv:1710.10903): multi-head edge-softmax attention.

Aggregation = SDDMM (edge scores) -> segment-softmax -> weighted SpMM.
Rubik applicability (DESIGN.md §4): LSH reordering accelerates the gather
phases (reuse distance of h_src rows); shared-set computation reuse is
INAPPLICABLE to the attention-weighted sum (per-destination weights break
order-invariant shared partials) — the paper's CR assumes uniform aggregators.

Layer ``l`` with ``H`` heads of ``F`` features (``z = h W``, no bias)::

    e_ij^h  = LeakyReLU(a_src^h . z_j^h + a_dst^h . z_i^h)     (j -> i)
    alpha^h = softmax of e^h over each node's in-edges (max-subtracted)
    m_i^h   = sum_j alpha_ij^h z_j^h

Options the arxiv variant (DGL's ogbn-arxiv GAT) adds, each off by default:
``self_loops`` puts one edge ``i -> i`` per node into every softmax and sum
(analytically: no edge is added); ``norm`` scales ``z_j`` by
``d_out(j)^-1/2`` before the sum and ``m_i`` by ``d_in(i)^+1/2`` after it
(degrees from ``graph["deg"]``/``graph["out_deg"]``, self loop counted); a
``"res"`` weight adds ``h R`` to every head; a final ``"b"`` biases the
head-averaged logits.

Edge tensors stay two-dimensional: scores heads-major ``(H, E)`` so that the
edges lie along the lanes, features ``(N, H * F)``.  Where a layer's
``H * F`` exceeds one 128-lane row, the weighted sum runs one head at a
time, each under ``jax.checkpoint``: one ``(E, F)`` gathered tensor is live
at once and the backward pass gathers it again rather than storing it.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import obs
from ..core.aggregate import segment_aggregate
from ..nn.layers import linear_init, linear_apply, cross_entropy

LANES = 128           # one TPU vector row: wider heads go one at a time


def gat_dims(d_in: int, d_hidden: int, n_heads: int, n_classes: int,
             n_layers: int = 2, final_heads: int = 1):
    """Static layer geometry (kept OUT of the params pytree so grad works)."""
    dims_in = [d_in] + [d_hidden * n_heads] * (n_layers - 1)
    dims_out = [d_hidden] * (n_layers - 1) + [n_classes]
    heads = [n_heads] * (n_layers - 1) + [final_heads]
    return dims_in, dims_out, heads


def gat_init(key, d_in: int, d_hidden: int, n_heads: int, n_classes: int,
             n_layers: int = 2, param_dtype=jnp.float32, *,
             final_heads: int = 1, residual: bool = False,
             final_bias: bool = False) -> Dict:
    """Hidden layers: d_in -> heads*hidden (concat); final: -> n_classes
    (mean over ``final_heads``).  ``residual`` adds a bias-free projection
    ``"res"`` to every layer, ``final_bias`` a ``"b"`` to the last."""
    dims_in, dims_out, heads = gat_dims(d_in, d_hidden, n_heads, n_classes,
                                        n_layers, final_heads)
    layers = []
    keys = jax.random.split(key, n_layers)
    for i in range(n_layers):
        k1, k2, k3 = jax.random.split(keys[i], 3)
        h = heads[i]
        layer = {
            "w": linear_init(k1, dims_in[i], h * dims_out[i], bias=False,
                             param_dtype=param_dtype),
            "a_src": (jax.random.normal(k2, (h, dims_out[i])) * 0.1
                      ).astype(param_dtype),
            "a_dst": (jax.random.normal(k3, (h, dims_out[i])) * 0.1
                      ).astype(param_dtype),
        }
        if residual:
            layer["res"] = linear_init(jax.random.fold_in(keys[i], 1),
                                       dims_in[i], h * dims_out[i],
                                       bias=False, param_dtype=param_dtype)
        if final_bias and i + 1 == n_layers:
            layer["b"] = jnp.zeros((dims_out[i],), param_dtype)
        layers.append(layer)
    return {"layers": layers}


def _per_edge(mask: jax.Array, like: jax.Array) -> jax.Array:
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


def edge_exp(scores: jax.Array, dst: jax.Array, num_nodes: int,
             edge_mask: Optional[jax.Array] = None,
             self_scores: Optional[jax.Array] = None):
    """The softmax's numerators over incoming edges per destination:
    ``exp(score - max)``, the maximum over the destination's live in-edges
    (and its self loop).  Returns ``(ex, ex_self)``; ``ex_self`` is None
    without ``self_scores``.

    scores: (E, ...), edges first (one head's ``(E,)`` row, or ``(E, H)``);
    self_scores: (N, ...), the scores of one self loop per node.  Masked
    edges get 0.  The subtracted maximum carries no gradient (the softmax
    does not depend on it), so the backward pass has no segment-max to
    differentiate.
    """
    if edge_mask is not None:
        scores = jnp.where(_per_edge(edge_mask, scores), scores, -jnp.inf)
    mx = jax.ops.segment_max(scores, dst, num_segments=num_nodes)
    if self_scores is not None:
        mx = jnp.maximum(mx, self_scores)
    mx = jax.lax.stop_gradient(jnp.where(jnp.isfinite(mx), mx, 0.0))
    ex = jnp.exp(scores - mx[dst])
    if edge_mask is not None:
        ex = jnp.where(_per_edge(edge_mask, ex), ex, 0.0)
    return ex, (None if self_scores is None
                else jnp.exp(self_scores - mx))


def edge_softmax(scores: jax.Array, dst: jax.Array, num_nodes: int,
                 edge_mask: Optional[jax.Array] = None) -> jax.Array:
    """Numerically-stable softmax over incoming edges per destination
    (arguments as :func:`edge_exp`)."""
    ex, _ = edge_exp(scores, dst, num_nodes, edge_mask)
    den = jax.ops.segment_sum(ex, dst, num_segments=num_nodes)
    return ex / jnp.maximum(den[dst], 1e-9)


def one_head_at_a_time(heads: int, features: int) -> bool:
    """Whether a layer's edge pass runs head by head: its ``H * F`` is
    wider than one 128-lane row."""
    return heads * features > LANES


def gat_layer(p, h: jax.Array, graph: Dict[str, Any], *,
              negative_slope: float = 0.2, self_loops: bool = False,
              norm: bool = False) -> jax.Array:
    """One layer's per-head outputs, concatenated: ``(N, H * F)``, the
    residual included.  Scopes: ``update`` (the projections),
    ``attention`` (scores and the softmax's numerators), ``aggregate``
    (the weighted sum).

    The scores come from ``h (W_h a^h)``, which equals ``(h W_h) a^h``, so
    no ``z`` is needed for them.  The softmax is normalised after the sum:
    each head's ``z_h`` goes into the edge pass with a column of ones, so
    that one scatter gives ``sum_j ex_ij z_j`` and its denominator
    ``sum_j ex_ij`` together (one pass over the edges fewer each way).
    Where :func:`one_head_at_a_time`, each head's ``z_h = h W_h`` and sum
    run in a loop over the heads, each under ``jax.checkpoint``: the
    backward pass recomputes ``z_h`` and the gathered ``z_h[src]`` rather
    than storing them, and keeps only the sum.  Otherwise all heads go in
    one pass, each edge's weight repeated over its head's columns."""
    N = h.shape[0]
    H, F = p["a_src"].shape
    src, dst, mask = graph["src"], graph["dst"], graph.get("edge_mask")
    w = p["w"]["w"].astype(h.dtype)
    w_heads = w.reshape(w.shape[0], H, F)
    with jax.named_scope("attention"):
        s = (h @ jnp.einsum("dhf,hf->dh", w_heads, p["a_src"])).T   # (H, N)
        t = (h @ jnp.einsum("dhf,hf->dh", w_heads, p["a_dst"])).T
        leaky = lambda v: jax.nn.leaky_relu(v, negative_slope)
        ex, ex_self = [], []
        for k in range(H):          # (E,) rows: edges along the lanes
            a, b = edge_exp(leaky(s[k][src] + t[k][dst]), dst, N, mask,
                            leaky(s[k] + t[k]) if self_loops else None)
            ex.append(a)
            ex_self.append(b if self_loops else jnp.zeros(N, h.dtype))
    inv_out = (jax.lax.rsqrt(jnp.maximum(graph["out_deg"], 1.0))[:, None]
               if norm else None)
    sqrt_in = (jnp.sqrt(jnp.maximum(graph["deg"], 1.0))[:, None]
               if norm else None)
    ones = jnp.ones((N, 1), h.dtype)

    def heads_out(w, ex, ex_self):
        """``m`` of the ``k`` heads whose projection is ``w`` (d, k * F),
        softmax numerators ``ex`` (E, k) and ``ex_self`` (N, k), without
        the residual: (N, k * F)."""
        k = ex.shape[1]
        with jax.named_scope("update"):
            z = h @ w
        with jax.named_scope("aggregate"):
            if norm:
                z = z * inv_out
            zs = [z[:, i * F:(i + 1) * F] for i in range(k)]
            # per head F features and a 1: the sum and its denominator
            aug = jnp.concatenate([c for zi in zs for c in (zi, ones)], 1)
            weight = ex[:, 0] if k == 1 else jnp.repeat(ex, F + 1, axis=1)
            agg = checkpoint_name(segment_aggregate(
                aug, src, dst, N, edge_weight=weight), "gat_sum")
            out = []
            for i, zi in enumerate(zs):
                num = agg[:, i * (F + 1):(i + 1) * (F + 1) - 1]
                den = agg[:, (i + 1) * (F + 1) - 1:(i + 1) * (F + 1)]
                if self_loops:
                    num = num + ex_self[:, i:i + 1] * zi
                    den = den + ex_self[:, i:i + 1]
                out.append(num / jnp.maximum(den, 1e-30))
            m = jnp.concatenate(out, axis=1) if k > 1 else out[0]
            return m * sqrt_in if norm else m

    if one_head_at_a_time(H, F):    # a loop, so that one head runs at a time
        one = jax.checkpoint(
            lambda wk, a, b: heads_out(wk, a[:, None], b[:, None]),
            policy=jax.checkpoint_policies.save_only_these_names("gat_sum"))
        _, m = jax.lax.scan(
            lambda _, xs: (None, one(*xs)), None,
            (w_heads.transpose(1, 0, 2), jnp.stack(ex),
             jnp.stack(ex_self)))                               # (H, N, F)
        out = m.transpose(1, 0, 2).reshape(N, H * F)
    else:
        out = heads_out(w, jnp.stack(ex, axis=1), jnp.stack(ex_self, axis=1))
    if "res" in p:
        with jax.named_scope("update"):
            out = out + linear_apply(p["res"], h)
    return out


def gat_apply(params, x: jax.Array, graph: Dict[str, Any],
              act=jax.nn.elu, *, self_loops: bool = False,
              norm: bool = False) -> jax.Array:
    """Layer ``i`` traces under the name scope ``layer{i}``; each trace
    counts its edge passes as ``model.gat.edge_passes`` (``heads``: heads
    per pass)."""
    h = x
    n_layers = len(params["layers"])
    for i, p in enumerate(params["layers"]):
        # geometry recovered from parameter shapes (heads, d_out static)
        n_heads, d_out = p["a_src"].shape
        per_head = one_head_at_a_time(n_heads, d_out)
        obs.counter("model.gat.edge_passes", layer=i,
                    heads=1 if per_head else n_heads).inc(
                        n_heads if per_head else 1)
        with jax.named_scope(f"layer{i}"):
            out = gat_layer(p, h, graph, self_loops=self_loops, norm=norm)
            with jax.named_scope("update"):
                if i + 1 < n_layers:
                    h = act(out)                        # concat heads
                else:                                   # average the heads
                    h = sum(out[:, k * d_out:(k + 1) * d_out]
                            for k in range(n_heads)) / n_heads
                    if "b" in p:
                        h = h + p["b"]
    return h


def gat_loss(params, x, graph, labels, mask, **kw):
    logits = gat_apply(params, x, graph, **kw)
    return cross_entropy(logits, labels, mask.astype(jnp.float32))
