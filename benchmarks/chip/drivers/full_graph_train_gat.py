"""Full-graph GAT training: ``full_graph_train`` with a GAT in GCN's place.

Set-up first draws the configuration's GAT weights from the run's seed
with ``reference_gat.init`` (the seed's key folded with 1), and stops at
once where the program's model lacks what the configuration asks for
(heads, residual projections, a final bias): the tree of shapes that
``GNNBundle("gat").init_params`` gives must be the drawn weights' tree.
The program's own initial values are never used, so a fault in them cannot
reach both sides of the comparison.  It then builds the graph, reorders it with ``minhash_reorder`` and
``Graph.permute``, draws the features from the run's seed as
``full_graph_train`` does, and compiles one step:
``train.loop.make_train_step`` over ``GNNBundle("gat").loss_fn`` with
``train.optimizer.adam``.  The batch carries in- and out-degrees with the
self loop counted.  Everything else (the checked steps, the window, the
traced window, the footprint and the ``readings`` compared with the
reference) is ``full_graph_train``'s; the reference is
``reference_gat.py``, whose sound steps are computed once per run (the
planted readings of ``readings.py`` are each compared with them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import flops_gat
import graphs
import harness
import inputs
import reference
import reference_gat

base = harness.driver("full_graph_train")
HOST_SPANS, KIND, readings = base.HOST_SPANS, base.KIND, base.readings


def model_kw(cfg: dict) -> dict:
    """``GNNBundle("gat")``'s options from a configuration file."""
    return {"d_hidden": cfg["hidden_per_head"], "n_heads": cfg["heads"],
            "n_layers": cfg["layers"], "final_heads": cfg["final_heads"],
            "residual": cfg["residual"], "final_bias": cfg["final_bias"],
            "activation": cfg["activation"], "self_loops": cfg["self_loops"],
            "norm": cfg["norm"] == "symmetric"}


def geometry(cfg: dict):
    """Heads and features per head of each layer."""
    hidden = cfg["layers"] - 1
    return ([cfg["heads"]] * hidden + [cfg["final_heads"]],
            [cfg["hidden_per_head"]] * hidden + [cfg["classes"]])


def as_program(layers: list) -> dict:
    """The program's layout of ``reference_gat.init``'s weights; the leaves
    come in the same order in both."""
    out = []
    for p in layers:
        layer = {k: jnp.asarray(v) for k, v in p.items()}
        layer["w"] = {"w": layer["w"]}
        layer["res"] = {"w": layer["res"]}
        out.append(layer)
    return {"layers": out}


class Driver(base.Driver):
    def __init__(self, cfg: dict, traffic: dict, seed: int, timers):
        from repro.configs.families import GNNBundle
        from repro.core import minhash_reorder
        from repro.graph import Graph
        from repro.train.loop import make_train_step
        from repro.train.optimizer import adam

        self.cfg, self.traffic = cfg, traffic
        classes, d0 = cfg["classes"], cfg["graph"]["feat_dim"]
        bundle = GNNBundle("gat", model_kw(cfg), n_classes=classes)
        key = harness.seed_key(seed)
        heads, widths = geometry(cfg)
        self.params0 = jax.device_get(reference_gat.init(
            jax.random.fold_in(key, 1), d0, tuple(heads), tuple(widths)))
        params = as_program(self.params0)
        shapes = jax.eval_shape(lambda k: bundle.init_params(k, d0), key)
        if (jax.tree_util.tree_structure(shapes)
                != jax.tree_util.tree_structure(params)
                or [a.shape for a in jax.tree_util.tree_leaves(shapes)]
                != [a.shape for a in jax.tree_util.tree_leaves(params)]):
            got = [tuple(p["a_src"].shape) for p in shapes["layers"]]
            raise harness.BenchError(
                f"the program's GAT does not build this configuration "
                f"(heads x features {got}, residual and final bias asked)")

        data = graphs.build(cfg["graph"])
        g0 = Graph(src=data.src, dst=data.dst, num_nodes=data.num_nodes,
                   labels=data.labels, train_mask=data.train_mask)
        with timers("reorder_s"):
            perm = minhash_reorder(g0)
            g = g0.permute(perm)
        self.data = graphs.relabel(data, perm)
        self.x, _ = inputs.make(key, jnp.asarray(self.data.labels),
                                (d0, classes))
        self.schedule = None

        opt_cfg = cfg["optimizer"]
        opt = adam(opt_cfg["lr"])
        self.step = make_train_step(bundle.loss_fn("full_graph_sm",
                                                   executor=cfg["executor"]),
                                    opt, clip_norm=opt_cfg["clip_norm"])
        one = np.float32(1.0)
        self.batch = {
            "src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst),
            "edge_mask": jnp.ones(g.num_edges, bool),
            "labels": jnp.asarray(g.labels % classes),
            "train_mask": jnp.asarray(g.train_mask), "x": self.x,
            "deg": jnp.asarray(g.in_degrees().astype(np.float32) + one),
            "out_deg": jnp.asarray(np.bincount(
                g.src, minlength=g.num_nodes).astype(np.float32) + one)}
        self.params, self.opt_state = params, opt.init(params)

        # the first steps, through the window's own call, from the seed
        self.checked_losses = []
        for k in range(traffic["checked_steps"]):
            self.checked_losses.append(self._one_step())
            if k == 0:
                self.first_m = jax.device_get(self.opt_state["m"])
        self.params_after = jax.device_get(self.params)
        self.flops_per_step = flops_gat.gat_train_step(
            data.num_nodes, data.num_edges, d0, heads, widths)

    def reference_steps(self, matmul=reference.matmul_highest,
                        train_mask=None):
        """The reference's checked steps from this run's weights (on
        another training mask, for a planted fault)."""
        sound = matmul is reference.matmul_highest and train_mask is None
        if sound and getattr(self, "_sound", None) is not None:
            return self._sound
        d, opt_cfg = self.data, self.cfg["optimizer"]
        mask = d.train_mask if train_mask is None else train_mask
        out = reference_gat.train_steps(
            self.params0, self.x, jnp.asarray(d.src), jnp.asarray(d.dst),
            jnp.asarray(d.labels % self.cfg["classes"]), jnp.asarray(mask),
            steps=len(self.checked_losses), lr=opt_cfg["lr"],
            clip_norm=opt_cfg["clip_norm"], matmul=matmul)
        if sound:
            self._sound = out
        return out
