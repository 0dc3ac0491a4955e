"""Online GCN inference under open-loop traffic, at any depth.

``open_loop_serve`` with a session of the configuration's every hidden
width: ``make_session("gcn", hidden=[...])`` with the ``segment``
executor for its offline passes (the oracle rows and the cache's warm
payloads), since block-ELL plans at ogbn-arxiv's size do not fit the chip.
The session is built before the graph is reordered, so that a program whose
session takes one hidden width stops at once.  The cache, the batcher, the
compiled layer shapes, the warm-up, the window and the ``readings``
compared with the reference are ``open_loop_serve``'s.

The traced window asks for the nodes that follow the measured window's in
the traffic's stream.  ``loadgen`` draws every schedule's nodes as a
prefix of one fixed stream, so ``open_loop_serve``'s traced window would
ask again for nodes the measured window has just served: at this cell's
rate the output layer's cache still holds every one of them, each request
hits, and the trace holds no device operation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import graphs
import harness
import inputs
import loadgen

base = harness.driver("open_loop_serve")
HOST_SPANS, KIND, readings = base.HOST_SPANS, base.KIND, base.readings


class Driver(base.Driver):
    def __init__(self, cfg: dict, traffic: dict, seed: int, timers):
        from repro.core import minhash_reorder
        from repro.graph import Graph
        from repro.serve import (EmbeddingCache, MicroBatcher, ServeEngine,
                                 make_session)

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.data = data = graphs.build(cfg["graph"])
        self.dims = (data.feat_dim, *cfg["hidden"], cfg["classes"])
        self.x, params = inputs.make(harness.seed_key(seed),
                                     jnp.asarray(data.labels), self.dims)
        self.params0 = jax.device_get(params)
        g = Graph(src=data.src, dst=data.dst, num_nodes=data.num_nodes,
                  node_feat=np.asarray(self.x), labels=data.labels,
                  train_mask=data.train_mask)
        self.sess = make_session("gcn", g, hidden=list(cfg["hidden"]),
                                 out_dim=cfg["classes"], executor="segment")
        self.sess.params = params
        with timers("reorder_s"):
            order = minhash_reorder(g)
        self.cache = EmbeddingCache(
            self.sess.layer_dims, int(traffic["cache_kb"] * 1024),
            order=order, line_size=traffic["line_size"],
            num_nodes=data.num_nodes)
        self.engine = ServeEngine(
            self.sess, self.cache,
            MicroBatcher(max_batch=traffic["max_batch"],
                         max_wait=traffic["max_wait_ms"] * 1e-3),
            oracle_check=False)
        self.engine.warm(order)
        self._compile_layers()
        self._open_loop(self._schedule(traffic["warmup_seconds"], seed + 1))

    def traced_window(self, seconds: float, annotate) -> None:
        self._skip = len(self.run["t"])
        try:
            super().traced_window(seconds, annotate)
        finally:
            self._skip = 0

    def _schedule(self, seconds: float, seed: int) -> loadgen.Schedule:
        """``open_loop_serve``'s schedule; while ``_skip`` is set, its nodes
        are the ``_skip + 1``-th and later picks of the traffic's stream
        (``loadgen.zipf_poisson``'s arithmetic), in an order from ``seed``."""
        sched = super()._schedule(seconds, seed)
        if not getattr(self, "_skip", 0):
            return sched
        t, n = self.traffic, self.data.num_nodes
        fixed = np.random.default_rng(t["popularity_seed"])
        p = np.arange(1, n + 1, dtype=np.float64) ** (-float(t["zipf_a"]))
        p /= p.sum()
        relabel = fixed.permutation(n)
        picks = relabel[fixed.choice(n, size=self._skip + len(sched), p=p)]
        order = np.random.default_rng(seed).permutation(len(sched))
        return loadgen.Schedule(t=sched.t,
                                nodes=picks[self._skip:][order].astype(
                                    np.int64))
