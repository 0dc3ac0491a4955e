"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gcn-cora --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --reduced --steps 10

Full configs target the production mesh (run under the dry-run first);
--reduced trains the arch family's smoke config on local devices — the same
code path end to end (config -> bundle -> jit train step -> checkpoints).
"""
import argparse
import importlib

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..configs import get
from .runtime import enable_compile_cache, float32_matmuls
from ..train import adam, fit, lm_token_batches, recsys_batches


def lm_reduced_driver(arch: str, steps: int, ckpt: str):
    mod = importlib.import_module("repro.configs." + arch.replace("-", "_"))
    cfg = mod.REDUCED
    from ..models import lm_init, lm_loss
    params = lm_init(jax.random.PRNGKey(0), cfg)
    loss_fn = lambda p, b: lm_loss(p, jnp.asarray(b["tokens"]),
                                   jnp.asarray(b["targets"]), cfg)
    return fit(loss_fn, adam(1e-3), params,
               lm_token_batches(cfg.vocab, 4, 64), steps=steps, ckpt_dir=ckpt)


def gnn_graph():
    """The graph every GNN run trains on: Cora-shaped, LSH-reordered."""
    from ..graph import cora_like
    from ..core import minhash_reorder
    g = cora_like()
    return g.permute(minhash_reorder(g))


def gnn_driver(arch: str, steps: int, ckpt: str, executor: str = "auto",
               schedule=None):
    """Train a GNN arch on :func:`gnn_graph`.  For GCN, ``schedule`` (one
    ``repro.exec`` layer candidate per layer) builds exactly that
    whole-forward schedule instead of choosing one."""
    spec = get(arch)
    bundle = spec.bundle()
    g = gnn_graph()
    exec_plan = None
    layer_plans = None
    if bundle.arch == "gcn" and (schedule is not None or executor in
                                 ("auto", "forward", "fused")):
        # default hot path: WHOLE-FORWARD scheduling — the repro.exec DP
        # picks every layer's (order, fuse, backend, bm, compact) jointly.
        # "auto"/"forward" additionally race the DP schedule against the
        # per-layer-greedy and cold-model schedules as measured whole-chain
        # fwd+bwd passes and cache the verdict on disk; "fused" trusts the
        # DP over the cache/FLOP-byte model without measuring
        from ..exec import (autotune_forward, build_forward_plan, gcn_chain,
                            plan_forward)
        dims = [g.node_feat.shape[1], *bundle.model_kw["hidden"],
                bundle.n_classes]
        specs = gcn_chain(dims)
        if schedule is not None:
            layer_plans = build_forward_plan(g, specs, schedule)
            print("forward schedule: explicit")
        elif executor in ("auto", "forward"):
            layer_plans, rec = autotune_forward(g, specs)
            obs.counter("exec.forward.verdict", source=rec.source).inc()
            obs.gauge("exec.forward.verdict_us").set(rec.us)
            greedy = rec.greedy_us
            print(f"forward autotune: schedule={rec.source} "
                  f"{rec.us:.0f}us whole-chain"
                  + (f" (per-layer-greedy {greedy:.0f}us, "
                     f"{rec.speedup_vs_greedy:.2f}x)"
                     if greedy is not None else "")
                  + (" (cached)" if rec.from_cache else ""))
        else:
            layer_plans = plan_forward(g, specs)
        for i, (s, lp) in enumerate(zip(specs, layer_plans.layers)):
            print(f"layer {i} ({s.d_in}->{s.d_out}): order={lp.order} "
                  f"fuse={lp.fuse} {lp.backend} bm={lp.gplan.bm} "
                  f"compact={lp.gplan.compact} "
                  f"buckets={lp.gplan.buckets or '-'}")
    elif bundle.arch == "gcn" and executor == "blockell":
        # the PR 3 path: fused aggregation, separate update matmul
        from ..exec import build_plan
        exec_plan = build_plan(g, "gcn")
    elif executor not in ("auto", "segment"):
        print(f"executor={executor!r} unsupported for arch {arch}; "
              "falling back to segment")
    if layer_plans is not None:
        loss_fn_builder = bundle.loss_fn("full_graph_sm", executor="fused",
                                         exec_plan=layer_plans)
    else:
        loss_fn_builder = bundle.loss_fn(
            "full_graph_sm",
            executor="blockell" if exec_plan is not None else "segment",
            exec_plan=exec_plan)
    params = bundle.init_params(jax.random.PRNGKey(0), g.node_feat.shape[1])
    deg = g.in_degrees().astype(np.float32) + 1.0
    batch = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst),
             "edge_mask": jnp.ones(g.num_edges, bool),
             "labels": jnp.asarray(g.labels % bundle.n_classes),
             "train_mask": jnp.asarray(g.train_mask),
             "x": jnp.asarray(g.node_feat), "deg": jnp.asarray(deg)}
    if bundle.arch == "nequip":
        batch["species"] = jnp.asarray(g.labels % 10)
        batch["pos"] = jnp.asarray(g.node_feat[:, :3])
        batch["energy_target"] = jnp.zeros(())
        for k in ("x", "deg"):
            batch.pop(k)
    return fit(lambda p, b: loss_fn_builder(p, b), adam(1e-2), params,
               iter(lambda: batch, None), steps=steps, ckpt_dir=ckpt)


def recsys_driver(arch: str, steps: int, ckpt: str):
    from ..configs.wide_deep import REDUCED as cfg
    from ..models import widedeep_init, widedeep_loss
    params = widedeep_init(jax.random.PRNGKey(0), cfg)
    loss_fn = lambda p, b: widedeep_loss(p, jnp.asarray(b["sparse"]),
                                         jnp.asarray(b["dense"]),
                                         jnp.asarray(b["labels"]), cfg)
    return fit(loss_fn, adam(1e-3), params, recsys_batches(cfg, 256),
               steps=steps, ckpt_dir=ckpt)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--dist", action="store_true",
                    help="shard the graph over all devices and route "
                         "aggregation through the halo exchange (GNN only); "
                         "use XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 for a CPU debug mesh")
    ap.add_argument("--parts", type=int, default=None,
                    help="number of graph shards for --dist "
                         "(default: device count)")
    ap.add_argument("--aggregator", default="halo",
                    choices=["halo", "allgather", "resilient"],
                    help="collective for --dist: the halo exchange, the "
                         "full-table allgather baseline, or the resilient "
                         "ladder (retry then per-step allgather fallback)")
    ap.add_argument("--executor", default="auto",
                    choices=["auto", "segment", "blockell", "fused",
                             "forward"],
                    help="GNN execution engine: 'forward' (and 'auto', "
                         "which prefers it) schedules the WHOLE forward — "
                         "a repro.exec DP picks every layer's (order, "
                         "fusion, backend, block shape, compaction) jointly "
                         "and races the schedule against per-layer-greedy "
                         "as measured whole-chain fwd+bwd, caching the "
                         "verdict on disk; 'fused' trusts the DP over the "
                         "cache/FLOP-byte model without measuring; "
                         "'blockell' keeps the PR 3 aggregation-only plan "
                         "+ separate matmul")
    obs.add_cli_flags(ap)
    ap.add_argument("--summary", action="store_true",
                    help="after the run, print the repro.obs.summary "
                         "one-pager for --metrics-out / --trace files")
    args = ap.parse_args(argv)
    if args.summary and not (args.metrics_out or args.trace):
        ap.error("--summary needs --metrics-out and/or --trace")
    spec = get(args.arch)
    enable_compile_cache()
    try:
        with obs.observed_run(args.metrics_out, args.trace), \
                float32_matmuls():
            if args.dist:
                if spec.family != "gnn":
                    ap.error(f"--dist supports GNN archs; {args.arch} is "
                             f"family '{spec.family}'")
                from ..dist import train_distributed
                # --ckpt under --dist writes buddy-mirrored checkpoints
                # (quorum restore survives one lost shard directory)
                res = train_distributed(args.arch, steps=args.steps,
                                        parts=args.parts,
                                        aggregator=args.aggregator,
                                        ckpt_dir=args.ckpt,
                                        ckpt_every=10 if args.ckpt else 0)
                losses = res["losses"]
                print(f"{args.arch} [dist]: {len(losses)} steps, loss "
                      f"{losses[0]:.4f} -> {losses[-1]:.4f}")
                return res
            driver = {"lm": lm_reduced_driver, "gnn": gnn_driver,
                      "recsys": recsys_driver}[spec.family]
            if spec.family == "gnn":
                res = driver(args.arch, args.steps, args.ckpt,
                             executor=args.executor)
            else:
                res = driver(args.arch, args.steps, args.ckpt)
            print(f"{args.arch}: {res.steps} steps, loss "
                  f"{res.losses[0]:.4f} -> {res.losses[-1]:.4f}, "
                  f"{res.wall_time:.1f}s, stragglers={res.straggler_flags}")
            return res
    finally:
        if args.summary:
            from ..obs import summary as _summary
            _summary.main([f for f in (args.metrics_out, args.trace) if f])


if __name__ == "__main__":
    main()
