"""Online GCN inference under open-loop traffic, as ``launch.serve`` serves.

Set-up builds the configuration's graph with the run's features, orders it
with the program's ``minhash_reorder``, and builds the serving stack the
launcher builds: ``make_session("gcn")`` at the configuration's widths with
the run's weights, an ``EmbeddingCache`` over that order, a
``MicroBatcher`` and a ``ServeEngine`` with its in-window oracle off.  The
cache is warmed along the order, every (layer, padded batch, padded edge
count) shape the session can meet on this graph is compiled, and
``warmup_seconds`` of the same traffic (from another seed) bring the cache
to its steady state.

The window replays the schedule of :mod:`loadgen` on the wall clock from
one thread: each request is handed to the batcher at its scheduled time or,
while a batch is being served, as soon as that batch ends; a batch is
served when it fills or when its oldest request has waited ``max_wait_ms``.
A request's latency runs from its scheduled arrival to its embedding on the
host.  Requests still queued when the last arrival is due are served
before the window closes.

Number compared with the reference: ``served_gap``, the largest gap between
a served embedding and the reference's row for that node, over every request
of the window, against the largest magnitude of the reference's output.
``unanswered`` counts requests that got no answer; its limit is 0.
"""
from __future__ import annotations

import contextlib
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import graphs
import harness
import inputs
import loadgen
import reference
import trace_reduce

HOST_SPANS = ("serve.submit", "serve.poll", "serve.wait",
              "serve.process_batch", "serve.expand", "serve.fetch_base",
              "serve.layer_forward")
KIND = "serve"


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def layer_shapes(in_degrees: np.ndarray, num_layers: int,
                 max_batch: int) -> set:
    """Every ``(layer, padded batch, padded edges)`` the engine can ask of a
    session on this graph: layer ``L`` computes at most ``max_batch``
    nodes, layer ``l - 1`` at most the nodes of layer ``l`` and their
    in-neighbours, and ``B`` nodes bring between the sum of the ``B``
    smallest and of the ``B`` largest in-degrees as edges."""
    n = in_degrees.shape[0]
    deg = np.sort(in_degrees.astype(np.int64))
    low, high = np.cumsum(deg), np.cumsum(deg[::-1])
    shapes = set()
    b_max = min(max_batch, n)
    for layer in range(num_layers, 0, -1):
        bp = 1
        while bp // 2 < b_max:
            b_lo, b_hi = bp // 2 + 1, min(bp, b_max)
            ep = _pow2(max(int(low[b_lo - 1]), 1))
            while ep <= _pow2(max(int(high[b_hi - 1]), 1)):
                shapes.add((layer, bp, ep))
                ep *= 2
            bp *= 2
        b_max = min(n, b_max + int(high[b_max - 1]))
    return shapes


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, timers):
        from repro.core import minhash_reorder
        from repro.graph import Graph
        from repro.serve import (EmbeddingCache, MicroBatcher, ServeEngine,
                                 make_session)

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.data = data = graphs.build(cfg["graph"])
        (hidden,) = cfg["hidden"]
        self.dims = (data.feat_dim, hidden, cfg["classes"])
        self.x, params = inputs.make(harness.seed_key(seed),
                                     jnp.asarray(data.labels), self.dims)
        self.params0 = jax.device_get(params)
        g = Graph(src=data.src, dst=data.dst, num_nodes=data.num_nodes,
                  node_feat=np.asarray(self.x), labels=data.labels,
                  train_mask=data.train_mask)
        with timers("reorder_s"):
            order = minhash_reorder(g)
        self.sess = make_session("gcn", g, hidden=hidden,
                                 out_dim=cfg["classes"])
        self.sess.params = params
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

    def _schedule(self, seconds: float, seed: int) -> loadgen.Schedule:
        t = self.traffic
        return loadgen.zipf_poisson(self.data.num_nodes, t["rate"], seconds,
                                    t["zipf_a"], t["popularity_seed"], seed)

    def _compile_layers(self) -> None:
        sess, dims = self.sess, self.dims
        for layer, bp, ep in sorted(layer_shapes(
                self.data.in_degrees(), sess.num_layers,
                self.traffic["max_batch"])):
            d = dims[layer - 1]
            sess.layer_forward(layer, np.zeros(bp, np.int32),
                               np.zeros(ep, np.int32), np.zeros(ep, np.int32),
                               np.zeros((ep, d), np.float32),
                               np.zeros((bp, d), np.float32))

    def program_bytes(self) -> int:
        """Nothing beyond the allocator's peak: the layer programs' largest
        arguments are a few thousand rows of at most 1433 floats."""
        return 0

    # --------------------------------------------------------------- window
    def _open_loop(self, sched: loadgen.Schedule,
                   annotate=lambda name: contextlib.nullcontext()) -> dict:
        from repro.serve.batcher import Request

        engine, batcher = self.engine, self.engine.batcher
        count, t_sched, nodes = len(sched), sched.t, sched.nodes
        submitted = np.full(count, np.nan)
        done = np.full(count, np.nan)
        answers = np.zeros((count, self.dims[-1]), np.float32)
        batch_s = []
        clock = time.perf_counter
        i = 0
        t0 = clock()

        def serve(mb):
            with annotate("serve.process_batch"):
                b0 = clock()
                emb = engine.process_batch(mb)
                b1 = clock()
            ids = [r.req_id for r in mb.requests]
            done[ids] = b1 - t0
            answers[ids] = emb
            batch_s.append(b1 - b0)

        while True:
            now = clock() - t0
            if i < count and t_sched[i] <= now:
                with annotate("serve.submit"):
                    submitted[i] = now
                    mb = batcher.submit(Request(i, int(nodes[i]),
                                                float(t_sched[i])))
                    i += 1
                if mb is not None:
                    serve(mb)
                continue
            with annotate("serve.poll"):
                mb = batcher.poll(now)
            if mb is not None:
                serve(mb)
                continue
            if i >= count and not batcher.pending:
                break
            due = batcher.due()
            nxt = min(t_sched[i] if i < count else np.inf,
                      due if due is not None else np.inf)
            wait = nxt - (clock() - t0)
            if wait > 2e-4:
                with annotate("serve.wait"):
                    time.sleep(wait - 1e-4)
        return {"t": t_sched, "nodes": nodes, "submitted": submitted,
                "done": done, "answers": answers, "batch_s": batch_s}

    def window(self, seconds: float) -> dict:
        self.cache.reset_stats()
        run = self._open_loop(self._schedule(seconds, self.seed))
        self.run = run
        latency = run["done"] - run["t"]
        answered = np.isfinite(latency)
        lat = np.where(answered, latency, np.inf)
        late = loadgen.lateness(run["t"], run["submitted"])
        return {"attempted": len(lat), "failed": int((~answered).sum()),
                "hit_rate": self.cache.stats().hit_rate,
                "batch_ms": float(np.mean(run["batch_s"])) * 1e3,
                "gen_late_p95_ms": loadgen.percentile(late, 95) * 1e3,
                "metrics": {
                    "serve_p50_ms": loadgen.percentile(lat, 50) * 1e3,
                    "serve_p95_ms": loadgen.percentile(lat, 95) * 1e3}}

    def traced_window(self, seconds: float, annotate) -> None:
        sess, cache = self.sess, self.cache
        wrapped = {(sess, "expand"): "serve.expand",
                   (sess, "layer_forward"): "serve.layer_forward",
                   (cache, "fetch_base"): "serve.fetch_base"}

        def spanned(fn, name):
            def call(*a, **kw):
                with annotate(name):
                    return fn(*a, **kw)
            return call
        for (obj, attr), name in wrapped.items():
            setattr(obj, attr, spanned(getattr(obj, attr), name))
        try:
            with annotate(trace_reduce.WINDOW):
                self._open_loop(self._schedule(seconds, self.seed + 2),
                                annotate)
        finally:
            for obj, attr in wrapped:
                delattr(obj, attr)

    def release(self) -> None:
        del self.engine, self.cache, self.sess
        gc.collect()
        jax.clear_caches()

    # ---------------------------------------------------------------- check
    def reference_rows(self, matmul=reference.matmul_highest) -> np.ndarray:
        d = self.data
        return np.asarray(reference.forward_jit(
            inputs.as_reference(self.params0), self.x, jnp.asarray(d.src),
            jnp.asarray(d.dst), matmul=matmul))

    def check(self) -> dict:
        return readings(self.run, self.reference_rows())


def readings(run: dict, ref: np.ndarray) -> dict:
    answered = np.isfinite(run["done"])
    gap = np.abs(run["answers"][answered] - ref[run["nodes"][answered]])
    return {"served_gap": float(gap.max(initial=0.0) / np.abs(ref).max()),
            "unanswered": int((~answered).sum())}
