"""Family bundles: uniform dry-run/train surface per architecture family.

Each bundle exposes:
  abstract_state(shape)                -> (params, opt_state) ShapeDtypeStructs
  input_specs(shape)                   -> dict of ShapeDtypeStructs
  step_fn(shape)                       -> callable to lower
  shardings(mesh, shape)               -> (arg_shardings, out_shardings)
The dry-run lowers step_fn with jit(in_shardings=...) over the abstract
state + inputs; nothing is ever materialized.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .base import LM_SHAPES, GNN_SHAPES, RECSYS_SHAPES, pad_to
from ..models.transformer import (LMConfig, lm_init, lm_loss, lm_prefill,
                                  lm_decode_step, make_kv_caches)
from ..models import (gcn_init, gcn_loss, gat_init, gat_loss, pna_init,
                      pna_loss, nequip_init, nequip_energy,
                      WideDeepConfig, widedeep_init, widedeep_loss,
                      widedeep_logits, retrieval_score)
from ..train.optimizer import adam, apply_updates, clip_by_global_norm
from ..dist.sharding import (lm_param_specs, batch_axes, to_shardings,
                              maybe_shard)

SDS = jax.ShapeDtypeStruct


def _spec_tree_for_opt(param_specs):
    return {"m": param_specs, "v": param_specs, "step": P()}


def _named(mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda s: isinstance(s, P))


# ===================================================================== LM
@dataclasses.dataclass
class LMBundle:
    cfg: LMConfig
    moments_dtype: Any = jnp.float32
    shapes = tuple(LM_SHAPES)

    # ------------------------------------------------------------- state
    def abstract_params(self):
        return jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), self.cfg))

    def opt(self):
        return adam(3e-4, moments_dtype=self.moments_dtype)

    def abstract_state(self, shape: str):
        params = self.abstract_params()
        if LM_SHAPES[shape]["kind"] != "train":
            return params, None
        opt_state = jax.eval_shape(self.opt().init, params)
        return params, opt_state

    # ------------------------------------------------------------- inputs
    def input_specs(self, shape: str) -> Dict[str, Any]:
        info = LM_SHAPES[shape]
        B, S = info["batch"], info["seq"]
        if info["kind"] == "train":
            return {"tokens": SDS((B, S), jnp.int32),
                    "targets": SDS((B, S), jnp.int32)}
        if info["kind"] == "prefill":
            return {"tokens": SDS((B, S), jnp.int32)}
        # decode: one new token against an S-long cache
        caches = jax.eval_shape(
            lambda: make_kv_caches(self.cfg, B, S))
        return {"token": SDS((B, 1), jnp.int32),
                "caches": caches,
                "cache_len": SDS((), jnp.int32)}

    # ------------------------------------------------------------- steps
    def make_constrain(self):
        """Per-layer weight sharding constraint applied INSIDE scan bodies
        (see lm_forward docstring).  Uses the ambient abstract mesh, so the
        same step function works on any mesh it's lowered under."""
        cfg = self.cfg

        def drop_lead(spec_tree, n):
            return jax.tree_util.tree_map(
                lambda s: P(*s[n:]), spec_tree,
                is_leaf=lambda s: isinstance(s, P))

        def constrain(kind, lp):
            from ..dist.sharding import ambient_mesh
            mesh = ambient_mesh()
            if mesh is None:
                return lp
            specs = lm_param_specs(cfg, mesh)
            key = "moe_layers" if kind == "moe" else "dense_layers"
            if key not in specs:
                return lp
            sub = drop_lead(specs[key], 1)

            def walk(spec, param):
                if isinstance(spec, P):
                    return jax.tree_util.tree_map(
                        lambda a: jax.lax.with_sharding_constraint(a, spec),
                        param)
                return {k: walk(spec[k], param[k]) for k in param}
            return walk(sub, lp)
        return constrain

    def step_fn(self, shape: str):
        info = LM_SHAPES[shape]
        cfg = self.cfg
        cn = self.make_constrain()
        if info["kind"] == "train":
            opt = self.opt()

            def train_step(params, opt_state, batch):
                def loss_fn(p):
                    return lm_loss(p, batch["tokens"], batch["targets"], cfg,
                                   constrain=cn)
                loss, grads = jax.value_and_grad(loss_fn)(params)
                grads, _ = clip_by_global_norm(grads, 1.0)
                updates, opt_state2 = opt.update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state2, loss
            return train_step
        if info["kind"] == "prefill":
            def prefill_step(params, batch):
                return lm_prefill(params, batch["tokens"], cfg, constrain=cn)
            return prefill_step

        def decode_step(params, batch):
            return lm_decode_step(params, batch["token"], batch["caches"],
                                  batch["cache_len"], cfg, info["seq"],
                                  constrain=cn)
        return decode_step

    # ---------------------------------------------------------- shardings
    def _cache_spec(self, mesh: Mesh, batch: int):
        """KV cache PartitionSpec factory for the stacked cache trees."""
        ba = batch_axes(mesh)
        n_batch_shards = (mesh.shape["data"] *
                          (mesh.shape.get("pod", 1)))
        if batch >= n_batch_shards and batch % n_batch_shards == 0:
            bspec, sspec = ba, "model"
        else:
            bspec = None
            sspec = tuple(a for a in mesh.axis_names)  # shard seq everywhere

        def spec(leaf):
            lead = (None,) * (leaf.ndim - 4)
            return P(*lead, bspec, sspec, None, None)
        return spec

    def shardings(self, mesh: Mesh, shape: str):
        info = LM_SHAPES[shape]
        pspecs = lm_param_specs(self.cfg, mesh)
        params_sh = _tree_specs_to_shardings(pspecs, self.abstract_params(),
                                             mesh)
        ba = batch_axes(mesh)
        if info["kind"] == "train":
            opt_sh = {"m": params_sh, "v": params_sh,
                      "step": NamedSharding(mesh, P())}
            batch_sh = {"tokens": NamedSharding(mesh, P(ba, None)),
                        "targets": NamedSharding(mesh, P(ba, None))}
            out_sh = (params_sh, opt_sh, NamedSharding(mesh, P()))
            return (params_sh, opt_sh, batch_sh), out_sh
        if info["kind"] == "prefill":
            batch_sh = {"tokens": NamedSharding(mesh, P(ba, None))}
            return (params_sh, batch_sh), None
        # decode
        spec = self._cache_spec(mesh, info["batch"])
        caches = self.input_specs(shape)["caches"]
        cache_sh = jax.tree_util.tree_map(
            lambda leaf: NamedSharding(mesh, spec(leaf)), caches)
        tok_spec = (P(ba, None) if info["batch"] >= mesh.shape["data"]
                    else P(None, None))
        batch_sh = {"token": NamedSharding(mesh, tok_spec),
                    "caches": cache_sh,
                    "cache_len": NamedSharding(mesh, P())}
        out_sh = (NamedSharding(mesh, tok_spec), cache_sh)
        return (params_sh, batch_sh), out_sh


def _tree_specs_to_shardings(spec_tree, params_tree, mesh):
    """Broadcast a structural spec tree over the params tree (specs may be
    single P leaves standing for whole sub-pytrees of identical layout)."""
    def walk(spec, param):
        if isinstance(spec, P):
            return jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, spec), param)
        if isinstance(spec, dict):
            return {k: walk(spec[k], param[k]) for k in param}
        if isinstance(spec, (list, tuple)):
            return type(spec)(walk(s, p) for s, p in zip(spec, param))
        raise TypeError(type(spec))
    return walk(spec_tree, params_tree)


# ==================================================================== GNN
@dataclasses.dataclass
class GNNBundle:
    """gcn | gat | pna | nequip over the 4 graph cells."""

    arch: str
    model_kw: Dict[str, Any]
    n_classes: int = 16
    shapes = tuple(GNN_SHAPES)

    # cell geometry (padded to 512-divisible static shapes)
    def geometry(self, shape: str) -> Dict[str, int]:
        info = GNN_SHAPES[shape]
        if shape == "minibatch_lg":
            b, (f1, f2) = info["batch_nodes"], info["fanout"]
            n = b + b * f1 + b * f1 * f2
            e = b * f1 + b * f1 * f2
            d = info["d_feat"]
        elif shape == "molecule":
            n = info["batch"] * info["n_nodes"]
            e = info["batch"] * info["n_edges"]
            d = 16
        else:
            n, e, d = info["n_nodes"], info["n_edges"], info["d_feat"]
        return {"n": pad_to(n, 512), "e": pad_to(e, 512), "d": d}

    def init_params(self, key, d_feat: int):
        if self.arch == "gcn":
            return gcn_init(key, [d_feat, *self.model_kw["hidden"],
                                  self.n_classes])
        if self.arch == "gat":
            kw = self.model_kw
            return gat_init(key, d_feat, kw["d_hidden"], kw["n_heads"],
                            self.n_classes, kw["n_layers"],
                            final_heads=kw.get("final_heads", 1),
                            residual=kw.get("residual", False),
                            final_bias=kw.get("final_bias", False))
        if self.arch == "pna":
            return pna_init(key, d_feat, self.model_kw["d_hidden"],
                            self.model_kw["n_layers"], self.n_classes)
        if self.arch == "nequip":
            return nequip_init(key, channels=self.model_kw["d_hidden"],
                               n_layers=self.model_kw["n_layers"],
                               n_rbf=self.model_kw.get("n_rbf", 8),
                               cutoff=self.model_kw.get("cutoff", 5.0))
        raise ValueError(self.arch)

    def abstract_state(self, shape: str):
        g = self.geometry(shape)
        params = jax.eval_shape(
            lambda: self.init_params(jax.random.PRNGKey(0), g["d"]))
        opt_state = jax.eval_shape(adam(1e-3).init, params)
        return params, opt_state

    def input_specs(self, shape: str):
        g = self.geometry(shape)
        n, e, d = g["n"], g["e"], g["d"]
        base = {"src": SDS((e,), jnp.int32), "dst": SDS((e,), jnp.int32),
                "edge_mask": SDS((e,), jnp.bool_),
                "labels": SDS((n,), jnp.int32),
                "train_mask": SDS((n,), jnp.bool_)}
        if self.arch == "nequip":
            base["species"] = SDS((n,), jnp.int32)
            base["pos"] = SDS((n, 3), jnp.float32)
            base["energy_target"] = SDS((), jnp.float32)
        else:
            base["x"] = SDS((n, d), jnp.float32)
            base["deg"] = SDS((n,), jnp.float32)
            if self.arch == "gat":
                base["out_deg"] = SDS((n,), jnp.float32)
        return base

    def loss_fn(self, shape: str, executor: str = "segment",
                exec_plan=None):
        """``executor="blockell"`` + a ``repro.exec.GraphExecutionPlan``
        routes GCN aggregation through the fused block-ELL engine;
        ``executor="fused"`` + a per-layer list of
        ``repro.exec.LayerExecutionPlan`` — or a whole-forward
        ``repro.exec.ForwardExecutionPlan`` (DP-scheduled layer chain) —
        folds the update matmul in too (the plans are closed over; their
        custom VJPs keep the loss differentiable)."""
        if executor == "blockell" and exec_plan is None:
            raise ValueError("executor='blockell' needs an exec_plan "
                             "(repro.exec.build_plan / autotune_plan)")
        if executor == "fused" and not exec_plan:
            raise ValueError("executor='fused' needs per-layer plans "
                             "(repro.exec.build_layer_plan / "
                             "autotune_layer_plan / plan_forward)")
        g = self.geometry(shape)

        def loss(params, batch):
            if self.arch == "nequip":
                e = nequip_energy(params, batch["species"], batch["pos"],
                                  batch["src"], batch["dst"],
                                  edge_mask=batch["edge_mask"],
                                  node_mask=batch["train_mask"].astype(
                                      jnp.float32))
                return jnp.mean((jnp.sum(e) - batch["energy_target"]) ** 2)
            graph = {"src": batch["src"], "dst": batch["dst"],
                     "edge_mask": batch["edge_mask"], "deg": batch["deg"],
                     "out_deg": batch.get("out_deg"), "mean_log_deg": 2.0}
            mask = batch["train_mask"]
            if self.arch == "gcn":
                return gcn_loss(params, batch["x"], graph, batch["labels"],
                                mask, executor=executor, ell=exec_plan)
            if self.arch == "gat":
                kw = self.model_kw
                return gat_loss(params, batch["x"], graph, batch["labels"],
                                mask,
                                act=getattr(jax.nn,
                                            kw.get("activation", "elu")),
                                self_loops=kw.get("self_loops", False),
                                norm=kw.get("norm", False))
            if self.arch == "pna":
                return pna_loss(params, batch["x"], graph, batch["labels"],
                                mask)
            raise ValueError(self.arch)
        return loss

    def step_fn(self, shape: str):
        opt = adam(1e-3)
        loss_fn = self.loss_fn(shape)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            grads, _ = clip_by_global_norm(grads, 1.0)
            updates, opt_state2 = opt.update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state2, loss
        return train_step

    def shardings(self, mesh: Mesh, shape: str):
        axes = tuple(mesh.axis_names)
        params, opt_state = self.abstract_state(shape)
        rep = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), params)
        opt_sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), opt_state)
        node = NamedSharding(mesh, P(axes))
        node2 = NamedSharding(mesh, P(axes, None))
        edge = NamedSharding(mesh, P(axes))
        batch_sh = {"src": edge, "dst": edge, "edge_mask": edge,
                    "labels": node, "train_mask": node}
        if self.arch == "nequip":
            batch_sh.update({"species": node, "pos": node2,
                             "energy_target": NamedSharding(mesh, P())})
        else:
            batch_sh.update({"x": node2, "deg": node})
            if self.arch == "gat":
                batch_sh["out_deg"] = node
        out_sh = (rep, opt_sh, NamedSharding(mesh, P()))
        return (rep, opt_sh, batch_sh), out_sh


# ================================================================= recsys
@dataclasses.dataclass
class RecsysBundle:
    cfg: WideDeepConfig
    shapes = tuple(RECSYS_SHAPES)

    def abstract_state(self, shape: str):
        params = jax.eval_shape(
            lambda: widedeep_init(jax.random.PRNGKey(0), self.cfg))
        if RECSYS_SHAPES[shape]["kind"] != "train":
            return params, None
        return params, jax.eval_shape(adam(1e-3).init, params)

    def input_specs(self, shape: str):
        info = RECSYS_SHAPES[shape]
        B = info["batch"]
        base = {"sparse": SDS((B, self.cfg.n_sparse), jnp.int32),
                "dense": SDS((B, self.cfg.n_dense), jnp.float32)}
        if info["kind"] == "train":
            base["labels"] = SDS((B,), jnp.float32)
        if shape == "retrieval_cand":
            base["candidates"] = SDS((info["n_candidates"],
                                      self.cfg.mlp_dims[-1]), jnp.float32)
        return base

    def step_fn(self, shape: str):
        cfg = self.cfg
        info = RECSYS_SHAPES[shape]
        if info["kind"] == "train":
            opt = adam(1e-3)

            def train_step(params, opt_state, batch):
                def loss_fn(p):
                    return widedeep_loss(p, batch["sparse"], batch["dense"],
                                         batch["labels"], cfg)
                loss, grads = jax.value_and_grad(loss_fn)(params)
                updates, opt_state2 = opt.update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state2, loss
            return train_step
        if shape == "retrieval_cand":
            def retrieve(params, batch):
                return retrieval_score(params, batch["sparse"],
                                       batch["dense"], batch["candidates"],
                                       cfg)
            return retrieve

        def serve(params, batch):
            return widedeep_logits(params, batch["sparse"], batch["dense"],
                                   cfg)
        return serve

    def shardings(self, mesh: Mesh, shape: str):
        info = RECSYS_SHAPES[shape]
        ba = batch_axes(mesh)
        axes = tuple(mesh.axis_names)
        params, opt_state = self.abstract_state(shape)
        pspec = {"table": P("model", None), "wide": P("model"),
                 "wide_dense": {"w": P(None, None), "b": P(None)},
                 "deep": [{"w": P(None, None), "b": P(None)}
                          for _ in range(len(self.cfg.mlp_dims) + 1)]}
        params_sh = _tree_specs_to_shardings(pspec, params, mesh)
        bspec = ba if info["batch"] >= mesh.devices.size // mesh.shape["model"] \
            else None
        batch_sh = {"sparse": NamedSharding(mesh, P(bspec, None)),
                    "dense": NamedSharding(mesh, P(bspec, None))}
        if info["kind"] == "train":
            opt_sh = {"m": params_sh, "v": params_sh,
                      "step": NamedSharding(mesh, P())}
            batch_sh["labels"] = NamedSharding(mesh, P(bspec))
            out_sh = (params_sh, opt_sh, NamedSharding(mesh, P()))
            return (params_sh, opt_sh, batch_sh), out_sh
        if shape == "retrieval_cand":
            batch_sh["sparse"] = NamedSharding(mesh, P(None, None))
            batch_sh["dense"] = NamedSharding(mesh, P(None, None))
            batch_sh["candidates"] = NamedSharding(mesh, P(axes, None))
            return (params_sh, batch_sh), NamedSharding(mesh, P(axes))
        return (params_sh, batch_sh), None
