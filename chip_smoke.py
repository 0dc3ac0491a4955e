"""Smoke run of the main path on a TPU, through the normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the mesh path only

One chip: (a) the device is a TPU; (b) the four block-ELL Pallas kernels,
compiled (not interpreted), match a plain segment-sum reference at Cora's
widths (1433 -> 16 -> 7); (c) ``launch.train`` trains the 2-layer GCN with
the default executor and with a schedule forced onto the Pallas layer
plans, and the loss falls; (d) ``launch.serve`` serves 200 Cora requests
and passes its oracle check.  Four chips: halo-exchange training over a
4-way mesh agrees with the allgather baseline from the same init.

Everything runs in this one process; no other process is started.  Each
phase prints its wall seconds, with compilation counted separately: they are
set-up facts, not performance numbers.  Any failed check exits non-zero.
The last line of standard output is one JSON object naming the device; it
is printed only when every phase passed.  Without a TPU, or without the
repository's ``src/`` beside this file, the script fails before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PARITY_TOL = 1e-4         # max |pallas - reference|, relative to max(1, |ref|)
FORCED_PALLAS = (("aggregate_first", True, "pallas", 128, True),) * 2


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# ------------------------------------------------------------ bookkeeping
class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@contextlib.contextmanager
def phase(name: str, stats: CompileStats):
    c0, h0, t0 = stats.compile_s, stats.cache_hits, time.perf_counter()
    print(f"== phase {name}", flush=True)
    yield
    print(f"== phase {name}: wall {time.perf_counter() - t0:.1f}s "
          f"(backend compile {stats.compile_s - c0:.1f}s, "
          f"persistent-cache hits {stats.cache_hits - h0}; set-up, "
          f"not a speed result)", flush=True)


def counter_total(prefix: str) -> int:
    from repro import obs
    return sum(v for k, v in obs.snapshot()["counters"].items()
               if k == prefix or k.startswith(prefix + "{"))


def losses_fall(losses, what: str) -> None:
    losses = np.asarray(losses, np.float64)
    check(losses.size >= 2 and np.isfinite(losses).all(),
          f"{what}: non-finite losses {losses.tolist()}")
    check(losses[-1] < losses[0],
          f"{what}: loss did not fall {losses.tolist()}")
    print(f"{what}: loss {losses[0]:.4f} -> {losses[-1]:.4f}", flush=True)


# ------------------------------------------------------------ (a) device
def tpu_devices(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} ({devs[0].device_kind})")
    check(len(devs) >= count, f"need {count} TPU devices, have {len(devs)}")
    print(f"device: {devs[0].device_kind} x{len(devs)}", flush=True)
    return devs


# ---------------------------------------------------- (b) kernel parity
def segment_reference(g):
    """Segment-sum GCN reference: A_hat x = s (A (s x) + s x), s =
    rsqrt(deg + 1)."""
    import jax
    import jax.numpy as jnp
    from repro.core.aggregate import segment_aggregate

    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
    s = jax.lax.rsqrt(jnp.asarray(g.in_degrees(), jnp.float32) + 1.0)

    def agg(x):
        xs = x * s[:, None]
        return (segment_aggregate(xs, src, dst, g.num_nodes, op="sum")
                + xs) * s[:, None]

    def layer(x, w, b, relu):
        y = agg(x) @ w + b
        return jnp.maximum(y, 0.0) if relu else y

    return agg, layer


def parity(name: str, y, ref) -> float:
    y, ref = np.asarray(y), np.asarray(ref)
    check(y.shape == ref.shape, f"{name}: shape {y.shape} != {ref.shape}")
    check(np.isfinite(y).all(), f"{name}: non-finite output")
    err = float(np.max(np.abs(y - ref)) / max(1.0, np.max(np.abs(ref))))
    print(f"parity {name}: shape {y.shape} err {err:.2e}", flush=True)
    check(err <= PARITY_TOL, f"{name}: err {err:.2e} > {PARITY_TOL}")
    return err


def kernel_parity(g) -> None:
    import jax
    import jax.numpy as jnp
    from repro.exec import build_layer_plan, build_plan

    agg, layer = segment_reference(g)
    rng = np.random.default_rng(0)
    x = jnp.asarray(g.node_feat)
    h = jnp.asarray(rng.standard_normal((g.num_nodes, 16)), jnp.float32)
    dims = ((x, 16), (h, 7))
    # compact (spmm_blockell_compact / _update_compact), padded
    # (spmm_blockell_fused / _update), and degree-bucketed compact
    # sub-grids (the TPU bucket candidate's x_diag / s_in_diag operands)
    variants = (("compact", dict(compact=True)),
                ("padded", dict(compact=False)),
                ("bucketed", dict(compact=True, buckets="128@7+256")))
    for label, kw in variants:
        gp = build_plan(g, "gcn", bm=128, backend="pallas", **kw)
        check(not gp.meta_fwd.interpret, f"{label}: Pallas interpret mode")
        for xin, d_out in dims:
            d_in = xin.shape[1]
            parity(f"aggregate/{label} d={d_in}",
                   jax.jit(gp.raw_apply)(xin), agg(xin))
            lp = build_layer_plan(g, "gcn", d_in=d_in, d_out=d_out,
                                  order="aggregate_first", fuse=True,
                                  gplan=gp)
            w = jnp.asarray(rng.standard_normal((d_in, d_out))
                            / np.sqrt(d_in), jnp.float32)
            b = jnp.asarray(rng.standard_normal(d_out), jnp.float32)
            relu = d_out != 7
            y = jax.jit(lambda x, w, b: lp.apply(x, w, b, relu=relu))(
                xin, w, b)
            parity(f"layer/{label} {d_in}->{d_out}", y,
                   layer(xin, w, b, relu))


# ------------------------------------------------------------ (c) training
def training(g) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get
    from repro.exec import build_forward_plan, gcn_chain
    from repro.launch import train
    from repro.models.gcn import gcn_apply, make_graph_inputs

    res = train.main(["--arch", "gcn-cora", "--steps", "5"])
    losses_fall(res.losses, "train --executor auto")

    # the same model forced onto the compiled Pallas layer plans: forward
    # parity against the segment reference, then training through it
    bundle = get("gcn-cora").bundle()
    dims = [g.node_feat.shape[1], *bundle.model_kw["hidden"],
            bundle.n_classes]
    fp = build_forward_plan(g, gcn_chain(dims), FORCED_PALLAS)
    params = bundle.init_params(jax.random.PRNGKey(0), dims[0])
    graph = make_graph_inputs(g)
    x = jnp.asarray(g.node_feat)
    y = jax.jit(lambda p, x: gcn_apply(p, x, graph, "fused", ell=fp))(
        params, x)
    y_ref = gcn_apply(params, x, graph, "segment")
    parity("forward pallas-forced vs segment", y, y_ref)
    res = train.gnn_driver("gcn-cora", 5, None, schedule=FORCED_PALLAS)
    losses_fall(res.losses, "train pallas-forced")


# ------------------------------------------------------------ (d) serving
def serving() -> None:
    from repro.launch import serve
    # serve.main exits 1 itself when its oracle check fails
    rep = serve.main(["--graph", "cora", "--model", "gcn",
                      "--requests", "200"])
    check(rep.num_requests == 200, f"served {rep.num_requests} of 200")


# ------------------------------------------------------- four-chip mesh
def mesh_training(devs) -> None:
    from repro.dist import train_distributed

    runs = {agg: train_distributed("gcn-cora", steps=10, parts=4,
                                   aggregator=agg)
            for agg in ("halo", "allgather")}
    for agg, res in runs.items():
        losses_fall(res["losses"], f"dist {agg}")
        for name, a in res["batch"].items():
            shard_devs = a.sharding.device_set
            check(len(shard_devs) == 4
                  and all(d.platform == "tpu" for d in shard_devs),
                  f"dist {agg}: {name} spans {shard_devs}")
            rows = {s.data.shape[0] for s in a.addressable_shards}
            check(rows == {a.shape[0] // 4},
                  f"dist {agg}: {name} shard rows {rows} of {a.shape[0]}")
    halo = np.asarray(runs["halo"]["losses"])
    ag = np.asarray(runs["allgather"]["losses"])
    diff = float(np.max(np.abs(halo - ag)))
    print(f"dist halo vs allgather: max |loss diff| {diff:.2e} over "
          f"{halo.size} steps", flush=True)
    check(diff <= PARITY_TOL * max(1.0, float(np.max(np.abs(ag)))),
          f"halo and allgather losses disagree by {diff:.2e}")


def run_phases(chips: int, devs, stats: CompileStats) -> None:
    """The four-chip mesh phase, or the one-chip phases (b)-(d)."""
    from repro.launch.train import gnn_graph
    if chips == 4:
        with phase("mesh training (4 chips)", stats):
            mesh_training(devs)
        return
    g = gnn_graph()
    with phase("kernel parity", stats):
        kernel_parity(g)
    with phase("training", stats):
        training(g)
    with phase("serving", stats):
        serving()
    failed = counter_total("exec.autotune.failed")
    fallbacks = counter_total("exec.fallback")
    print(f"exec.autotune.failed={failed} exec.fallback={fallbacks}")
    check(failed == 0, f"{failed} autotune candidates failed")
    check(fallbacks == 0, f"{fallbacks} backend fallbacks")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = tpu_devices(args.chips)
    from repro import obs
    from repro.launch.runtime import enable_compile_cache, float32_matmuls

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    obs.enable()
    stats = CompileStats()
    # float32 matmuls throughout, as in the launchers
    with float32_matmuls():
        run_phases(args.chips, devs, stats)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
