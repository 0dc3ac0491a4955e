"""Model sessions: the registry layer that lets one engine serve them all.

A *session* owns model parameters plus everything the engine needs to turn a
batch of node ids into embeddings:

* ``num_layers`` / ``layer_dims`` — the cache geometry (layer 0 = leaf
  inputs, layer ``num_layers`` = the served embedding);
* ``expand(nodes)`` — one-hop frontier growth (graph models only);
* ``gather(ids)`` — leaf values: an HBM feature fetch for GNNs, a
  user-tower compute for the recsys scorer (whose "graph" is one level deep);
* ``layer_forward(...)`` — one GNN layer over flat edge lists, numerically
  identical to the offline full-graph executor given full neighborhoods and
  global degrees;
* ``layer_values(l)`` — offline reference values for layer ``l`` over all
  nodes: the oracle (``l == num_layers``) and the ``warm()`` payloads.

Register new models in ``SESSION_BUILDERS``; ``make_session`` is the only
entry point the launcher and benchmarks use.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from .batcher import pow2_bucket as _pow2
from ..graph.structure import Graph
from ..graph.sampler import FullNeighborhood, NeighborSampler
from ..models.gcn import gcn_init, gcn_apply, make_graph_inputs
from ..models.sage_gin import sage_init, sage_apply
from ..models.recsys import WideDeepConfig, widedeep_init, user_tower
from ..nn.layers import linear_apply


# ------------------------------------------------------------ jitted layers
# One compilation per (model, padded-E, padded-B, dims, last?) — the pow2
# padding below keeps that set logarithmic in traffic size.
@functools.partial(jax.jit, static_argnames=("is_last",))
def _gcn_layer(w, b, src_h, self_h, inv_src, inv_dst, dst_index, *, is_last):
    msgs = src_h * inv_src[:, None]
    agg = jax.ops.segment_sum(msgs, dst_index, num_segments=self_h.shape[0])
    agg = (agg + self_h * inv_dst[:, None]) * inv_dst[:, None]
    h = agg @ w + b
    return h if is_last else jax.nn.relu(h)


@functools.partial(jax.jit, static_argnames=("is_last",))
def _sage_layer(w, b, src_h, self_h, edge_live, dst_index, *, is_last):
    B = self_h.shape[0]
    msgs = src_h * edge_live[:, None]
    s = jax.ops.segment_sum(msgs, dst_index, num_segments=B)
    cnt = jax.ops.segment_sum(edge_live, dst_index, num_segments=B)
    nbr = s / jnp.maximum(cnt, 1.0)[:, None]
    h = jnp.concatenate([self_h, nbr], axis=-1) @ w + b
    if not is_last:
        h = jax.nn.relu(h)
    return h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)


def _pad_pow2(a: np.ndarray, axis0: int) -> np.ndarray:
    """Zero-pad axis 0 to the given length."""
    pad = axis0 - a.shape[0]
    if pad == 0:
        return a
    cfg = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, cfg)


# ----------------------------------------------------------------- sessions
class GNNSession:
    """Serves a full-batch-trained GNN over sampled blocks.

    ``expander='full'`` (default) aggregates every in-edge with global
    degrees, so block outputs equal the offline full-graph forward row-for-row
    — the engine's oracle check is exact.  ``expander='fanout'`` swaps in the
    GraphSAGE sampler for approximate high-throughput serving.  ``hidden``
    is one width (two layers) or one width per hidden layer.
    """

    def __init__(self, name: str, g: Graph, kind: str,
                 hidden: Union[int, Sequence[int]] = 64, out_dim: int = 16,
                 seed: int = 0,
                 expander: str = "full", fanouts: Tuple[int, ...] = (10, 10),
                 executor: str = "fused"):
        assert g.node_feat is not None
        self.name = name
        self.g = g
        self.kind = kind
        self.executor = executor
        self.feats = np.asarray(g.node_feat, dtype=np.float32)
        d_in = self.feats.shape[1]
        widths = [hidden] if isinstance(hidden, int) else list(hidden)
        self.dims = [d_in, *widths, out_dim]
        key = jax.random.PRNGKey(seed)
        if kind == "gcn":
            self.params = gcn_init(key, self.dims)
            deg = g.in_degrees().astype(np.float32) + 1.0
            self.inv_sqrt = (1.0 / np.sqrt(np.maximum(deg, 1.0))).astype(np.float32)
        elif kind == "sage":
            self.params = sage_init(key, self.dims)
            self.inv_sqrt = None
        else:
            raise ValueError(kind)
        self._expander = (FullNeighborhood(g) if expander == "full"
                          else NeighborSampler(g, list(fanouts), seed=seed))
        self._layer_cache: Optional[List[np.ndarray]] = None
        # the offline full-graph passes (oracle rows + warm payloads) run on
        # the compiled exec engines; "segment" keeps the reference path.
        # "fused" (default) compiles the WHOLE forward through
        # repro.exec.plan_forward: the DP over the layer chain picks every
        # layer's (order, fuse, backend, bm, compact) jointly — measured
        # costs when the autotune cache is warm, the FLOP/byte model when
        # cold — and layers with matching configs share one graph plan.
        # SAGE layers use the two-W epilogue (one plan call per layer).
        mode = "gcn" if kind == "gcn" else "mean"
        self._plan = None
        self._fplan = None
        self._layer_plans = None
        if executor == "fused":
            from ..exec import plan_forward, gcn_chain, sage_chain
            specs = (gcn_chain(self.dims) if kind == "gcn"
                     else sage_chain(self.dims))
            self._fplan = plan_forward(g, specs)
            self._layer_plans = self._fplan.layers
        elif executor == "blockell":
            from ..exec import build_plan
            self._plan = build_plan(g, mode)

    # ------------------------------------------------------------ geometry
    @property
    def num_layers(self) -> int:
        return len(self.dims) - 1

    @property
    def layer_dims(self) -> List[int]:
        return list(self.dims)

    # ------------------------------------------------------------- serving
    def expand(self, nodes: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        with obs.span("serve.session.expand", cat="serve"):
            return self._expander.expand(nodes)

    def gather(self, ids: np.ndarray) -> np.ndarray:
        return self.feats[np.asarray(ids, dtype=np.int64)]

    def layer_forward(self, l: int, dst_ids: np.ndarray, edge_src: np.ndarray,
                      dst_index: np.ndarray, src_h: np.ndarray,
                      self_h: np.ndarray) -> np.ndarray:
        """One layer on the device, in three spans inside
        ``serve.session.layer``: ``pad`` (host-side padding to the pow2
        bucket), ``launch`` (the jitted call, host-to-device included) and
        ``readback`` (the wait on the device and the copy back)."""
        with obs.span("serve.session.layer", cat="serve", layer=l):
            with obs.span("serve.session.pad", cat="serve"):
                B, E = self_h.shape[0], src_h.shape[0]
                Bp, Ep = _pow2(B), _pow2(max(E, 1))
                p = self.params["layers"][l - 1]
                w = p["w"].astype(jnp.float32)
                b = p["b"].astype(jnp.float32)
                src_h_p = _pad_pow2(src_h.astype(np.float32), Ep)
                self_h_p = _pad_pow2(self_h.astype(np.float32), Bp)
                dst_p = _pad_pow2(dst_index.astype(np.int32), Ep)
                is_last = l == self.num_layers
                if self.kind == "gcn":
                    inv_src = _pad_pow2(self.inv_sqrt[edge_src], Ep)
                    inv_dst = _pad_pow2(self.inv_sqrt[dst_ids], Bp)
                else:
                    live = _pad_pow2(np.ones(E, np.float32), Ep)
            with obs.span("serve.session.launch", cat="serve"):
                if self.kind == "gcn":
                    out = _gcn_layer(w, b, src_h_p, self_h_p, inv_src,
                                     inv_dst, dst_p, is_last=is_last)
                else:
                    out = _sage_layer(w, b, src_h_p, self_h_p, live, dst_p,
                                      is_last=is_last)
            with obs.span("serve.session.readback", cat="serve"):
                return np.asarray(out)[:B]

    # -------------------------------------------------------------- oracle
    def layer_values(self, l: int) -> np.ndarray:
        """Offline full-graph values of layer ``l`` for every node."""
        if self._layer_cache is None:
            self._layer_cache = self._offline_layers()
        return self._layer_cache[l]

    def oracle(self, ids: np.ndarray) -> np.ndarray:
        return self.layer_values(self.num_layers)[np.asarray(ids, np.int64)]

    def _offline_layers(self) -> List[np.ndarray]:
        """Offline full-graph forward (the reference executors, *not* the
        serving path), capturing each layer's output as the next layer
        consumes it — post-activation for non-final layers.  These are the
        oracle rows and the payloads ``warm`` preloads.  With the default
        ``executor="fused"`` each layer is one call into the DP-scheduled
        ForwardExecutionPlan — the oracle is produced by the very plans the
        training path runs (SAGE through the two-W epilogue)."""
        from ..models.gcn import _aggregate
        from ..models.sage_gin import _agg

        h = jnp.asarray(self.feats)
        vals = [self.feats]
        L = self.num_layers
        lps = self._layer_plans
        if self.kind == "gcn":
            graph = make_graph_inputs(self.g)
            for i, p in enumerate(self.params["layers"]):
                if lps is not None:
                    h = lps[i].apply(h, p["w"], p.get("b"), relu=i + 1 < L)
                else:
                    agg = (self._plan.apply(h) if self._plan is not None
                           else _aggregate(h, graph, "segment"))
                    h = linear_apply(p, agg)
                    if i + 1 < L:
                        h = jax.nn.relu(h)
                vals.append(np.asarray(h))
        else:
            graph = {"src": jnp.asarray(self.g.src),
                     "dst": jnp.asarray(self.g.dst)}
            if self.g.edge_mask is not None:
                graph["edge_mask"] = jnp.asarray(self.g.edge_mask)
            for i, p in enumerate(self.params["layers"]):
                if lps is not None:
                    # the two-W epilogue: self and neighbor halves of the
                    # concat-form W in ONE plan call (ReLU folded in)
                    d_self = p["w"].shape[0] // 2
                    h = lps[i].apply(h, p["w"][d_self:], p.get("b"),
                                     w_self=p["w"][:d_self],
                                     relu=i + 1 < L)
                else:
                    nbr = (self._plan.apply(h) if self._plan is not None
                           else _agg(h, graph, "mean"))
                    h = linear_apply(p, jnp.concatenate([h, nbr], axis=-1))
                    if i + 1 < L:
                        h = jax.nn.relu(h)
                h = h / jnp.maximum(
                    jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
                vals.append(np.asarray(h))
        return vals


class WideDeepSession:
    """Recsys scorer session: one level deep, the leaf compute IS the model.

    Each "node id" is a user; their sparse/dense features are a deterministic
    function of the id (a stand-in for a feature store), and the served
    embedding is the wide&deep user tower.  ``num_layers == 0`` means the
    engine's whole job is dedupe + cache + batched tower compute.
    """

    def __init__(self, name: str, num_users: int,
                 cfg: Optional[WideDeepConfig] = None, seed: int = 0):
        self.name = name
        self.num_users = num_users
        self.cfg = cfg or WideDeepConfig(rows_per_field=1000,
                                         mlp_dims=(64, 32, 16))
        self.params = widedeep_init(jax.random.PRNGKey(seed), self.cfg)
        self._tower = jax.jit(
            lambda p, ids, dense: user_tower(p, ids, dense, self.cfg))

    @property
    def num_layers(self) -> int:
        return 0

    @property
    def layer_dims(self) -> List[int]:
        return [self.cfg.mlp_dims[-1]]

    def features(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Deterministic per-user feature-store stand-in."""
        u = np.asarray(ids, dtype=np.int64)[:, None]
        f = np.arange(self.cfg.n_sparse, dtype=np.int64)[None, :]
        sparse = ((u * 2654435761 + f * 40503 + 7) %
                  self.cfg.rows_per_field).astype(np.int32)
        k = np.arange(self.cfg.n_dense, dtype=np.int64)[None, :]
        dense = (((u * 97 + k * 31 + 13) % 1000) / 1000.0 - 0.5).astype(np.float32)
        return sparse, dense

    def gather(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, dtype=np.int64)
        Bp = _pow2(max(ids.shape[0], 1))
        sparse, dense = self.features(
            np.concatenate([ids, np.zeros(Bp - ids.shape[0], np.int64)]))
        out = self._tower(self.params, jnp.asarray(sparse), jnp.asarray(dense))
        return np.asarray(out)[:ids.shape[0]]

    def layer_values(self, l: int) -> np.ndarray:
        assert l == 0
        return self.gather(np.arange(self.num_users))

    def oracle(self, ids: np.ndarray) -> np.ndarray:
        return self.gather(ids)


# ----------------------------------------------------------------- registry
def _build_widedeep(g, **kw):
    num_users = kw.pop("num_users", g.num_nodes if g is not None else 4096)
    return WideDeepSession("wide_deep", num_users=num_users, **kw)


SESSION_BUILDERS: Dict[str, Callable[..., object]] = {
    "gcn": lambda g, **kw: GNNSession("gcn", g, "gcn", **kw),
    "sage_gin": lambda g, **kw: GNNSession("sage_gin", g, "sage", **kw),
    "wide_deep": _build_widedeep,
}


def make_session(model: str, g: Optional[Graph] = None, **kw):
    """Build a registered serving session (``gcn`` | ``sage_gin`` | ``wide_deep``)."""
    try:
        build = SESSION_BUILDERS[model]
    except KeyError:
        raise ValueError(f"unknown serve model {model!r}; "
                         f"registered: {sorted(SESSION_BUILDERS)}") from None
    return build(g, **kw)
