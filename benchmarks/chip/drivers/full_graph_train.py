"""Full-graph GCN training, driven as ``launch.train.gnn_driver`` drives it.

Set-up builds the configuration's graph, reorders it with the program's
``minhash_reorder`` and ``Graph.permute``, schedules the layer plans
(``exec.autotune_forward`` for ``auto``, held to one candidate per layer
where the configuration pins a ``schedule``; nothing for ``segment``), and
builds one compiled step: ``train.loop.make_train_step`` over
``GNNBundle("gcn").loss_fn`` with ``train.optimizer.adam``.  The first
``checked_steps`` steps go through that step from the seed's weights; the
plain reference follows them after the window.  The window then runs the
same step on the same state, each step ending with its loss on the host, as
``train.loop.fit`` does.

Numbers read against the reference (each a relative gap, the worst one; a
cell's ``limits`` file names those it compares):

* ``loss_gap``: the loss of each checked step;
* ``grad_gap``: the norm of each leaf of the first clipped gradient, as Adam
  got it (its first moment after one step over ``1 - b1``);
* ``grad_diff``: the norm of the difference between that gradient and the
  reference's, leaf by leaf.  The gap of two norms barely moves when each
  element is off by rounding in another direction; this number does, and
  it is the one that tells products at one step of precision below the
  configuration's from sound runs (see ``PERF.md``);
* ``change_gap``: the norm of each leaf's change over the checked steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's are left out: Adam moves them by round-off alone.

Each leaf's gap is taken against the larger of the reference's norm of that
leaf and of the median leaf.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import flops
import graphs
import harness
import inputs
import reference
import trace_reduce

HOST_SPANS = ("train.dispatch", "train.loss_sync")
KIND = "train"
STILL_LEAF = 1e-3          # of the median leaf's gradient norm


def _gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    scale = np.maximum(ref, np.median(ref))
    return np.abs(prog - ref) / scale


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, timers):
        from repro.configs.families import GNNBundle
        from repro.core import minhash_reorder
        from repro.graph import Graph
        from repro.train.loop import make_train_step
        from repro.train.optimizer import adam

        self.cfg, self.traffic = cfg, traffic
        data = graphs.build(cfg["graph"])
        g0 = Graph(src=data.src, dst=data.dst, num_nodes=data.num_nodes,
                   labels=data.labels, train_mask=data.train_mask)
        with timers("reorder_s"):
            perm = minhash_reorder(g0)
            g = g0.permute(perm)
        self.data = graphs.relabel(data, perm)
        self.dims = (data.feat_dim, *cfg["hidden"], cfg["classes"])
        self.x, params = inputs.make(harness.seed_key(seed),
                                     jnp.asarray(self.data.labels),
                                     self.dims)
        self.params0 = jax.device_get(params)

        bundle = GNNBundle("gcn", {"hidden": list(cfg["hidden"])},
                           n_classes=cfg["classes"])
        self.schedule = None
        if cfg["executor"] == "auto":
            from repro.exec import autotune_forward, gcn_chain
            pinned = cfg.get("schedule")
            cands = None if pinned is None else [[tuple(c)] for c in pinned]
            with timers("schedule_s"):
                plans, rec = autotune_forward(
                    g, gcn_chain(list(self.dims)), candidates=cands)
            self.schedule = {"source": rec.source, "us": rec.us,
                             "from_cache": rec.from_cache,
                             "layers": [list(c) for c in rec.configs]}
            loss_fn = bundle.loss_fn("full_graph_sm", executor="fused",
                                     exec_plan=plans)
        elif cfg["executor"] == "segment":
            loss_fn = bundle.loss_fn("full_graph_sm", executor="segment")
        else:
            raise harness.BenchError(f"executor {cfg['executor']!r}")
        opt_cfg = cfg["optimizer"]
        opt = adam(opt_cfg["lr"])
        self.step = make_train_step(loss_fn, opt,
                                    clip_norm=opt_cfg["clip_norm"])
        classes = cfg["classes"]
        self.batch = {
            "src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst),
            "edge_mask": jnp.ones(g.num_edges, bool),
            "labels": jnp.asarray(g.labels % classes),
            "train_mask": jnp.asarray(g.train_mask), "x": self.x,
            "deg": jnp.asarray(g.in_degrees().astype(np.float32) + 1.0)}
        self.params, self.opt_state = params, opt.init(params)

        # the first steps, through the window's own call, from the seed
        self.checked_losses = []
        for k in range(traffic["checked_steps"]):
            self.checked_losses.append(self._one_step())
            if k == 0:
                self.first_m = jax.device_get(self.opt_state["m"])
        self.params_after = jax.device_get(self.params)
        self.flops_per_step = flops.gcn_train_step(
            data.num_nodes, data.num_edges, self.dims)

    def _one_step(self) -> float:
        self.params, self.opt_state, loss = self.step(
            self.params, self.opt_state, self.batch)
        return float(loss)

    # --------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        step, batch = self.step, self.batch
        params, opt_state = self.params, self.opt_state
        steps = nonfinite = 0
        t0 = time.perf_counter()
        while True:
            params, opt_state, loss = step(params, opt_state, batch)
            loss = float(loss)
            steps += 1
            nonfinite += not math.isfinite(loss)
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        self.params, self.opt_state = params, opt_state
        return {"attempted": steps, "failed": nonfinite,
                "step_s": (t1 - t0) / steps,
                "metrics": {"step_ms": (t1 - t0) / steps * 1e3,
                            "train_edges_per_s":
                                self.data.num_edges * steps / (t1 - t0)}}

    def traced_window(self, seconds: float, annotate) -> None:
        step, batch = self.step, self.batch
        params, opt_state = self.params, self.opt_state
        t0 = time.perf_counter()
        with annotate(trace_reduce.WINDOW):
            while time.perf_counter() - t0 < seconds:
                with annotate("train.dispatch"):
                    params, opt_state, loss = step(params, opt_state, batch)
                with annotate("train.loss_sync"):
                    float(loss)
        self.params, self.opt_state = params, opt_state

    def program_bytes(self) -> int:
        """The compiled step's footprint on the chip (0 for a step that is
        not one jitted function, and so has no single program)."""
        lower = getattr(self.step, "lower", None)
        if lower is None:
            return 0
        return harness.compiled_bytes(
            lower(self.params, self.opt_state, self.batch).compile())

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        del self.step, self.params, self.opt_state, self.batch
        gc.collect()
        jax.clear_caches()

    # ---------------------------------------------------------------- check
    def reference_steps(self, matmul=reference.matmul_highest,
                        train_mask=None):
        """The reference's checked steps from this run's weights (on
        another training mask, for a planted fault)."""
        d, opt_cfg = self.data, self.cfg["optimizer"]
        mask = d.train_mask if train_mask is None else train_mask
        return reference.train_steps(
            inputs.as_reference(self.params0), self.x, jnp.asarray(d.src),
            jnp.asarray(d.dst), jnp.asarray(d.labels % self.cfg["classes"]),
            jnp.asarray(mask), steps=len(self.checked_losses),
            lr=opt_cfg["lr"], clip_norm=opt_cfg["clip_norm"], matmul=matmul)

    def check(self) -> dict:
        return readings(self.checked_losses, self.first_m, self.params0,
                        self.params_after, *self.reference_steps())


def _leaves(tree) -> list:
    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(tree)]


def _norms(arrays) -> np.ndarray:
    return np.array([np.linalg.norm(a) for a in arrays])


def readings(prog_losses, first_m, params0, params_after, ref_losses,
             ref_grad, ref_params) -> dict:
    """The numbers compared between a program run and the reference.  The
    trees may be in the program's layout or the reference's: their leaves
    come in the same order."""
    pl, rl = np.asarray(prog_losses, np.float64), np.asarray(ref_losses)
    g_prog = [m / (1.0 - reference.ADAM_B1) for m in _leaves(first_m)]
    g_ref = _leaves(ref_grad)
    g_ref_norm = _norms(g_ref)
    p0 = _leaves(params0)
    c_prog = _norms([a - b for a, b in zip(_leaves(params_after), p0)])
    c_ref = _norms([a - b for a, b in zip(_leaves(ref_params), p0)])
    moving = g_ref_norm >= STILL_LEAF * np.median(g_ref_norm)
    diff = _norms([a - b for a, b in zip(g_prog, g_ref)])
    return {"loss_gap": float(np.max(np.abs(pl - rl) / np.abs(rl))),
            "grad_gap": float(np.max(_gaps(_norms(g_prog), g_ref_norm))),
            "grad_diff": float(np.max(diff / np.maximum(
                g_ref_norm, np.median(g_ref_norm)))),
            "change_gap": float(np.max(_gaps(c_prog[moving],
                                             c_ref[moving])))}
