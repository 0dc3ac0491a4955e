"""Whole-forward scheduling (ISSUE 5): the DP over the layer chain vs
exhaustive enumeration, the generalized two-W / self-coeff layer kernels
(parity + grads vs unfused SAGE/GIN), cold-model vs warm-cache DP agreement,
the measured whole-forward autotune, and the cache-pruning satellite."""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.graph import Graph, synthesize, DatasetSpec
from repro.exec import (LayerSpec, ForwardCostOracle, build_cost_oracle,
                        dp_schedule, exhaustive_schedule, plan_forward,
                        build_forward_plan, autotune_forward, autotune_layer,
                        gcn_chain, sage_chain, gin_chain, chain_params,
                        build_plan, build_layer_plan, choose_order,
                        graph_fingerprint, prune_cache, cached_layer_costs,
                        model_layer_cost, residual_edge_cost,
                        plan_switch_cost)
import importlib
# the package re-exports the autotune FUNCTION under the submodule's name,
# so the module object must come from the import system directly
at = importlib.import_module("repro.exec.autotune")
from repro.models.sage_gin import (sage_init, sage_apply, sage_loss,
                                   gin_init, gin_apply, gin_loss)

KEY = jax.random.PRNGKey(0)
COO_CANDS = [("aggregate_first", False, "coo", 128, True),
             ("update_first", False, "coo", 128, True)]


def _random_graph(n, e, seed=0):
    rng = np.random.default_rng(seed)
    return Graph(src=rng.integers(0, n, e).astype(np.int32),
                 dst=rng.integers(0, n, e).astype(np.int32), num_nodes=n)


def _skewed_graph(n=1024, seed=1):
    rng = np.random.default_rng(seed)
    hub_src = rng.permutation(n).astype(np.int32)
    tail = np.arange(n - 1, dtype=np.int32)
    return Graph(src=np.concatenate([hub_src, tail]),
                 dst=np.concatenate([np.zeros(n, np.int32), tail + 1]),
                 num_nodes=n)


def _empty_row_graph(n=256):
    """Later row blocks have zero active slots: the fallback rows must go
    through the full two-W / self-coeff epilogue too."""
    rng = np.random.default_rng(2)
    return Graph(src=rng.integers(0, n, 400).astype(np.int32),
                 dst=rng.integers(0, 32, 400).astype(np.int32), num_nodes=n)


GRAPHS = {
    "random": _random_graph(300, 2000),
    "skewed": _skewed_graph(),
    "empty_rows": _empty_row_graph(),
}


def _inputs(g, d_in, d_out, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((g.num_nodes, d_in))
                    .astype(np.float32))
    w = jnp.asarray((rng.standard_normal((d_in, d_out)) / np.sqrt(d_in))
                    .astype(np.float32))
    ws = jnp.asarray((rng.standard_normal((d_in, d_out)) / np.sqrt(d_in))
                     .astype(np.float32))
    b = jnp.asarray(rng.standard_normal(d_out).astype(np.float32))
    return x, w, ws, b


# =========================================================== two-W epilogue
@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("backend", ["pallas", "jnp", "coo"])
@pytest.mark.parametrize("order", ["aggregate_first", "update_first"])
def test_two_w_self_coeff_parity(gname, backend, order):
    """Every (backend, order) — plus the one-launch fused kernels on pallas
    (padded AND slot-compacted) — matches the unfused two-W chain
    ``F(x) @ w + c * (x @ w_self) + b`` with a traced self coefficient."""
    g = GRAPHS[gname]
    x, w, ws, b = _inputs(g, 24, 8)
    c = jnp.asarray(1.7, jnp.float32)
    ref_plan = build_plan(g, "sum", bm=64, backend="coo")
    ref = np.asarray(jnp.maximum(ref_plan.apply(x) @ w + c * (x @ ws) + b,
                                 0.0))
    for compact in (True, False):
        gplan = build_plan(g, "sum", bm=64, backend=backend, compact=compact)
        fuses = [False]
        if backend == "pallas" and order == "aggregate_first":
            fuses.append(True)
        for fuse in fuses:
            lp = build_layer_plan(g, "sum", d_in=24, d_out=8, order=order,
                                  fuse=fuse, gplan=gplan)
            got = np.asarray(lp.apply(x, w, b, relu=True, w_self=ws,
                                      self_coeff=c))
            np.testing.assert_allclose(
                got, ref, atol=1e-5, rtol=1e-5,
                err_msg=f"{backend} {order} fuse={fuse} compact={compact}")


@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("order", ["aggregate_first", "update_first"])
def test_two_w_grads_vs_unfused(gname, order):
    """dx, dW, db, dW_self, dc through the generalized VJP == autodiff of
    the unfused chain, ≤1e-5 on skewed/random/empty-row graphs."""
    g = GRAPHS[gname]
    x, w, ws, b = _inputs(g, 12, 6, seed=7)
    c = jnp.asarray(1.3, jnp.float32)
    ref_plan = build_plan(g, "sum", bm=64, backend="coo")
    lp = build_layer_plan(g, "sum", d_in=12, d_out=6, order=order,
                          gplan=build_plan(g, "sum", bm=64, backend="jnp"))

    def ref_loss(x, w, b, ws, c):
        y = jnp.maximum(ref_plan.apply(x) @ w + c * (x @ ws) + b, 0.0)
        return jnp.sum(jnp.tanh(y))

    def lp_loss(x, w, b, ws, c):
        return jnp.sum(jnp.tanh(lp.apply(x, w, b, relu=True, w_self=ws,
                                         self_coeff=c)))

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2, 3, 4))(x, w, b, ws, c)
    g_lp = jax.grad(lp_loss, argnums=(0, 1, 2, 3, 4))(x, w, b, ws, c)
    for a, got, name in zip(g_ref, g_lp, ("dx", "dw", "db", "dws", "dc")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(got),
                                   atol=1e-5, rtol=1e-4,
                                   err_msg=f"{name} {order}")


def test_fused_pallas_two_w_grads():
    """The one-launch two-W kernel's VJP on the empty-row stress graph."""
    g = GRAPHS["empty_rows"]
    x, w, ws, b = _inputs(g, 16, 8, seed=9)
    c = jnp.asarray(0.8, jnp.float32)
    gplan = build_plan(g, "sum", bm=64, backend="pallas", compact=True)
    lp = build_layer_plan(g, "sum", d_in=16, d_out=8,
                          order="aggregate_first", fuse=True, gplan=gplan)
    ref_plan = build_plan(g, "sum", bm=64, backend="coo")

    def ref_loss(x, w, b, ws, c):
        y = jnp.maximum(ref_plan.apply(x) @ w + c * (x @ ws) + b, 0.0)
        return jnp.sum(jnp.tanh(y))

    def lp_loss(x, w, b, ws, c):
        return jnp.sum(jnp.tanh(lp.apply(x, w, b, relu=True, w_self=ws,
                                         self_coeff=c)))

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2, 3, 4))(x, w, b, ws, c)
    g_lp = jax.grad(lp_loss, argnums=(0, 1, 2, 3, 4))(x, w, b, ws, c)
    for a, got, name in zip(g_ref, g_lp, ("dx", "dw", "db", "dws", "dc")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(got),
                                   atol=1e-5, rtol=1e-4, err_msg=name)


def test_two_w_operand_validation():
    g = GRAPHS["random"]
    x, w, ws, b = _inputs(g, 12, 6)
    lp = build_layer_plan(g, "sum", d_in=12, d_out=6, backend="coo")
    with pytest.raises(ValueError, match="self_coeff needs w_self"):
        lp.apply(x, w, b, self_coeff=2.0)
    with pytest.raises(ValueError, match="w_self must match"):
        lp.apply(x, w, b, w_self=w.T)


# ==================================================== SAGE / GIN one-launch
def test_sage_fused_one_call_matches_segment():
    """SAGE through the two-W epilogue (one plan call per layer, ReLU
    folded) == the segment concat form, values and grads."""
    g = synthesize(DatasetSpec("s", 300, 1800, 12, 3, community=0.9,
                               num_communities=5, seed=6))
    graph = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst)}
    x = jnp.asarray(g.node_feat)
    params = sage_init(KEY, [12, 8, 5])
    fp = plan_forward(g, sage_chain([12, 8, 5]), candidates=[COO_CANDS])
    ref = sage_apply(params, x, graph, executor="segment")
    got = sage_apply(params, x, graph, executor="fused", plan=fp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    labels = jnp.asarray(g.labels % 5)
    mask = jnp.asarray(g.train_mask)
    g_seg = jax.grad(sage_loss)(params, x, graph, labels, mask,
                                executor="segment")
    g_fus = jax.grad(sage_loss)(params, x, graph, labels, mask,
                                executor="fused", plan=fp)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
        g_seg, g_fus)


def test_sage_fused_pallas_one_launch_parity():
    """The whole SAGE layer as ONE fused Pallas launch (two-W epilogue)."""
    g = GRAPHS["random"]
    x = jnp.asarray(np.random.default_rng(3)
                    .standard_normal((g.num_nodes, 12)).astype(np.float32))
    graph = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst)}
    params = sage_init(KEY, [12, 8, 5])
    gplan = build_plan(g, "mean", bm=64, backend="pallas", compact=True)
    plans = [build_layer_plan(g, "mean", d_in=12, d_out=8,
                              order="aggregate_first", fuse=True,
                              gplan=gplan),
             build_layer_plan(g, "mean", d_in=8, d_out=5,
                              order="aggregate_first", fuse=True,
                              gplan=gplan)]
    ref = sage_apply(params, x, graph, executor="segment")
    got = sage_apply(params, x, graph, executor="fused", plan=plans)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_gin_fused_matches_segment_with_eps_grads():
    """GIN's (1+ε)h + F(h) through the self-coeff epilogue: values and ALL
    grads — including the traced ε — match the segment path."""
    g = synthesize(DatasetSpec("g", 300, 1800, 12, 4, community=0.9,
                               num_communities=5, seed=7))
    graph = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst)}
    x = jnp.asarray(g.node_feat)
    params = gin_init(KEY, 12, 8, 3, 4)
    fp = plan_forward(g, gin_chain(12, 8, 3), candidates=[COO_CANDS])
    ref = gin_apply(params, x, graph, executor="segment")
    got = gin_apply(params, x, graph, executor="fused", plan=fp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    labels = jnp.asarray(g.labels % 4)
    mask = jnp.asarray(g.train_mask)
    g_seg = jax.grad(gin_loss)(params, x, graph, labels, mask,
                               executor="segment")
    g_fus = jax.grad(gin_loss)(params, x, graph, labels, mask,
                               executor="fused", plan=fp)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3),
        g_seg, g_fus)
    for ci, conv in enumerate(g_fus["convs"]):   # ε really gets a gradient
        assert np.isfinite(float(conv["eps"]))


def test_gin_fused_pallas_one_launch_parity():
    g = GRAPHS["empty_rows"]
    x = jnp.asarray(np.random.default_rng(4)
                    .standard_normal((g.num_nodes, 12)).astype(np.float32))
    graph = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst)}
    params = gin_init(KEY, 12, 8, 2, 3)
    gplan = build_plan(g, "sum", bm=64, backend="pallas", compact=True)
    plans = [build_layer_plan(g, "sum", d_in=12, d_out=8,
                              order="aggregate_first", fuse=True,
                              gplan=gplan),
             build_layer_plan(g, "sum", d_in=8, d_out=8,
                              order="aggregate_first", fuse=True,
                              gplan=gplan)]
    ref = gin_apply(params, x, graph, executor="segment")
    got = gin_apply(params, x, graph, executor="fused", plan=plans)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ========================================================= DP vs exhaustive
def _synthetic_oracle(specs, cands, seed, n=500, e=4000):
    """Random measured costs for every (layer, candidate): the DP must find
    the same optimum as brute force no matter what the numbers are."""
    rng = np.random.default_rng(seed)
    measured = tuple({c: float(rng.uniform(10, 1000)) for c in cands}
                     for _ in specs)
    return ForwardCostOracle(n=n, e=e, specs=tuple(specs),
                             cands=(tuple(cands),) * len(specs),
                             measured=measured, scale=1.0,
                             sources=("measured",) * len(specs))


@pytest.mark.parametrize("n_layers", [2, 3])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_dp_matches_exhaustive_synthetic(n_layers, seed):
    specs = gcn_chain([32] * (n_layers + 1))
    cands = [("aggregate_first", False, "coo", 128, True),
             ("update_first", False, "coo", 128, True),
             ("aggregate_first", False, "jnp", 64, True),
             ("update_first", False, "jnp", 64, True)]
    oracle = _synthetic_oracle(specs, cands, seed)
    c_dp, p_dp = dp_schedule(oracle)
    c_ex, p_ex = exhaustive_schedule(oracle)
    assert abs(c_dp - c_ex) < 1e-9
    assert p_dp == p_ex


def test_dp_matches_exhaustive_real_oracle(tmp_path):
    """Same check on the real cost oracle (cold model + residual/sharing
    edge costs) over a real graph, 3-layer chain."""
    g = GRAPHS["random"]
    specs = gcn_chain([64, 16, 32, 8])
    oracle = build_cost_oracle(g, specs, cache_dir=str(tmp_path))
    c_dp, p_dp = dp_schedule(oracle)
    c_ex, p_ex = exhaustive_schedule(oracle)
    assert abs(c_dp - c_ex) < 1e-6 * max(abs(c_ex), 1.0)
    assert p_dp == p_ex


def test_edge_costs_shape_the_schedule():
    """The residual edge term penalizes aggregate-first-unfused by the
    boundary width; the switch term is zero exactly for shared configs."""
    af = ("aggregate_first", False, "coo", 128, True)
    af_fused = ("aggregate_first", True, "pallas", 128, True)
    uf = ("update_first", False, "coo", 128, True)
    assert residual_edge_cost(1000, 64, af) == 2.0 * 1000 * 64 * 4
    assert residual_edge_cost(1000, 64, af_fused) == 0.0
    assert residual_edge_cost(1000, 64, uf) == 0.0
    assert plan_switch_cost(5000, af, uf) == 0.0          # same engine
    assert plan_switch_cost(5000, af, af_fused) > 0.0     # coo -> pallas
    # fusion credit: the fused candidate is cheaper than unfused agg-first
    spec = LayerSpec(32, 16)
    unfused = model_layer_cost(1000, 5000, spec, af)
    fused = model_layer_cost(1000, 5000, spec, af_fused)
    assert fused < unfused


# ============================================== cold vs warm DP agreement
def _seed_layer_cache(path, g, spec, rows, platform):
    """Write a synthetic measured table for one layer into the disk cache
    (the format autotune_layer stores and cached_layer_costs reads)."""
    key = (f"{graph_fingerprint(g)}:layer:{spec.d_in}x{spec.d_out}:"
           f"{spec.mode}:r{int(spec.relu)}b{int(spec.bias)}:{platform}:"
           "deadbeef")
    entries = {}
    if os.path.exists(path):
        entries = json.load(open(path))
    best = min(rows, key=lambda r: r[-1])
    entries[key] = {"order": best[0], "fuse": best[1], "backend": best[2],
                    "bm": best[3], "compact": best[4], "us": best[5],
                    "model_order": best[0], "table": [list(r) for r in rows]}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    json.dump(entries, open(path, "w"))


def test_dp_cold_vs_warm_agreement(tmp_path):
    """When the measured tables mirror the FLOP/byte model's ordering, the
    warm-cache DP must pick the same schedule as the cold-model DP."""
    g = GRAPHS["random"]
    specs = gcn_chain([96, 12, 4])
    platform = jax.default_backend()
    path = os.path.join(str(tmp_path), "autotune.json")
    n, e = g.num_nodes, g.num_valid_edges
    for spec in specs:
        rows = [list(c) + [model_layer_cost(n, e, spec, c) / 1000.0]
                for c in COO_CANDS]
        _seed_layer_cache(path, g, spec, rows, platform)
    cold = build_cost_oracle(g, specs, candidates=[COO_CANDS],
                             cache_dir=str(tmp_path), use_cache=False)
    warm = build_cost_oracle(g, specs, candidates=[COO_CANDS],
                             cache_dir=str(tmp_path), use_cache=True)
    assert all(s == "model" for s in cold.sources)
    assert all(s == "measured" for s in warm.sources)
    _, p_cold = dp_schedule(cold)
    _, p_warm = dp_schedule(warm)
    assert p_cold == p_warm
    # both shrinking layers stream the narrow side, like the order model
    assert all(c[0] == choose_order(n, e, s.d_in, s.d_out)
               for c, s in zip(p_cold, specs))


def test_cached_layer_costs_merges_tables(tmp_path):
    g = _random_graph(220, 1300, seed=5)
    spec = LayerSpec(32, 8)
    platform = jax.default_backend()
    path = os.path.join(str(tmp_path), "autotune.json")
    rows = [list(COO_CANDS[0]) + [111.0], list(COO_CANDS[1]) + [222.0]]
    _seed_layer_cache(path, g, spec, rows, platform)
    costs = cached_layer_costs(g, 32, 8, "gcn", cache_dir=str(tmp_path))
    assert costs[COO_CANDS[0]] == 111.0
    assert costs[COO_CANDS[1]] == 222.0
    # different shape -> cold
    assert cached_layer_costs(g, 8, 32, "gcn", cache_dir=str(tmp_path)) == {}


# ======================================================== plan + autotune
def test_plan_forward_shares_gplans():
    g = GRAPHS["random"]
    fp = plan_forward(g, gcn_chain([32, 16, 8]), candidates=[COO_CANDS])
    assert len(fp) == 2
    if fp.configs[0][2:] == fp.configs[1][2:]:
        assert fp.num_gplans == 1
    d = fp.describe()
    assert len(d["layers"]) == 2 and d["source"].startswith("dp")


def test_forward_plan_apply_chain_matches_manual():
    g = GRAPHS["random"]
    specs = gcn_chain([24, 12, 6])
    fp = plan_forward(g, specs, candidates=[COO_CANDS])
    params = chain_params(specs, seed=3)
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((g.num_nodes, 24)).astype(np.float32))
    ref_plan = build_plan(g, "gcn", bm=64, backend="coo")
    h = jnp.maximum(ref_plan.apply(x) @ params[0]["w"] + params[0]["b"], 0.0)
    ref = ref_plan.apply(h) @ params[1]["w"] + params[1]["b"]
    got = fp.apply_chain(x, params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_build_forward_plan_validates():
    g = GRAPHS["random"]
    specs = gcn_chain([16, 8, 4])
    with pytest.raises(ValueError, match="configs"):
        build_forward_plan(g, specs, [COO_CANDS[0]])
    with pytest.raises(ValueError, match="self_kind"):
        LayerSpec(16, 8, self_kind="sideways")
    with pytest.raises(ValueError, match="empty"):
        autotune_forward(g, [])


def test_autotune_forward_round_trip(tmp_path):
    """The measured whole-forward tuner: greedy is always in the race (so
    the winner can only match or beat per-layer tuning), the verdict caches,
    and the cached rebuild reproduces the winning configs."""
    g = _random_graph(220, 1300, seed=6)
    specs = gcn_chain([32, 16, 8])
    fp1, rec1 = autotune_forward(g, specs, candidates=[COO_CANDS],
                                 cache_dir=str(tmp_path), iters=1)
    assert not rec1.from_cache
    labels = [r[0] for r in rec1.table]
    assert "greedy" in labels
    assert rec1.us == min(us for _, us in rec1.table)
    assert rec1.greedy_us is not None
    assert rec1.us <= rec1.greedy_us
    assert all(c in COO_CANDS for c in rec1.configs)

    fp2, rec2 = autotune_forward(g, specs, candidates=[COO_CANDS],
                                 cache_dir=str(tmp_path), iters=1)
    assert rec2.from_cache
    assert rec2.configs == rec1.configs and rec2.source == rec1.source
    assert tuple(fp2.configs) == tuple(fp1.configs)

    rec3 = autotune_forward(g, specs, candidates=[COO_CANDS],
                            cache_dir=str(tmp_path), iters=1, force=True)[1]
    assert not rec3.from_cache
    # the whole-forward verdict lives in the same fingerprinted document
    entries = json.load(open(os.path.join(str(tmp_path), "autotune.json")))
    assert any(":forward:" in k and k.startswith(graph_fingerprint(g))
               for k in entries)


def test_autotune_forward_never_schedules_a_failed_candidate(tmp_path,
                                                            capsys):
    """A Pallas candidate that fails to run when raced is counted per
    backend and printed with its error, and no schedule in the whole-forward
    race contains it — the cold FLOP/byte DP credits fusion, so without the
    exclusion it would schedule the failed fused layer."""
    from repro import obs
    from repro.chaos import Fault, FaultPlan, armed
    g = _random_graph(220, 1300, seed=6)
    specs = gcn_chain([16, 64, 64])     # the cold DP fuses both layers
    fused = ("aggregate_first", True, "pallas", 128, True)

    def failed_count():
        return sum(v for k, v in obs.snapshot()["counters"].items()
                   if k.startswith("exec.autotune.failed{")
                   and "backend=pallas" in k)

    before = failed_count()
    with armed(FaultPlan.of(Fault("exec.pallas_launch", "kernel_launch",
                                  count=10 ** 6))):
        fp, rec = autotune_forward(g, specs, candidates=[COO_CANDS + [fused]],
                                   cache_dir=str(tmp_path), iters=1)
    assert failed_count() - before == len(specs)
    assert "autotune: candidate" in capsys.readouterr().out
    assert all(fused not in cfgs for _, cfgs in rec.schedules)
    assert all(lp.backend == "coo" for lp in fp.layers)


# ============================================================ cache prune
def test_prune_cache_keeps_most_recent(tmp_path):
    g = _random_graph(200, 1000, seed=7)
    # ten distinct layer shapes -> ten timestamped entries
    for d_out in range(2, 12):
        autotune_layer(g, 16, d_out, "gcn", candidates=COO_CANDS,
                       cache_dir=str(tmp_path), iters=1)
    path = os.path.join(str(tmp_path), "autotune.json")
    entries = json.load(open(path))
    assert len(entries) == 10
    assert all("_ts" in e for e in entries.values())
    newest = sorted(entries, key=lambda k: entries[k]["_ts"])[-3:]
    left = prune_cache(max_entries=3, cache_dir=str(tmp_path))
    assert left == 3
    assert sorted(json.load(open(path))) == sorted(newest)
    # pruning below the floor is idempotent
    assert prune_cache(max_entries=3, cache_dir=str(tmp_path)) == 3


def test_store_auto_prunes(tmp_path, monkeypatch):
    monkeypatch.setattr(at, "CACHE_MAX_ENTRIES", 4)
    g = _random_graph(200, 1000, seed=8)
    for d_out in range(2, 10):
        autotune_layer(g, 16, d_out, "gcn", candidates=COO_CANDS,
                       cache_dir=str(tmp_path), iters=1)
    entries = json.load(open(os.path.join(str(tmp_path), "autotune.json")))
    assert len(entries) == 4          # every store prunes to the cap
    # the most recent shapes survived
    assert any(":16x9:" in k for k in entries)
    assert not any(":16x2:" in k for k in entries)


# ======================================================== chain builders
def test_chain_builders():
    c = gcn_chain([32, 16, 8])
    assert [s.relu for s in c] == [True, False]
    assert all(s.mode == "gcn" and s.self_kind == "none" for s in c)
    s = sage_chain([12, 8, 5])
    assert all(x.self_kind == "two_w" and x.mode == "mean" for x in s)
    assert [x.relu for x in s] == [True, False]
    gi = gin_chain(12, 8, 3)
    assert len(gi) == 3
    assert all(x.self_kind == "self_coeff" and x.mode == "sum" and x.relu
               for x in gi)
    assert (gi[0].d_in, gi[0].d_out, gi[1].d_in) == (12, 8, 8)


# ============================== calibration feedback into the cold DP (PR 7)
def _flip_setup():
    """A 1-layer chain where both candidates are close enough that a skewed
    per-class calibration ratio can flip the cold DP's pick."""
    g = GRAPHS["random"]
    specs = gcn_chain([16, 16])
    cands = COO_CANDS
    return g, specs, cands


def test_skewed_calibration_flips_cold_dp(tmp_path):
    """The acceptance criterion: a calibration table that marks one
    candidate class as measured far slower than modeled must change the
    cold-DP schedule."""
    from repro.obs.audit import class_key
    g, specs, cands = _flip_setup()
    base = build_cost_oracle(g, specs, candidates=[cands],
                             cache_dir=str(tmp_path), use_cache=False,
                             use_calibration=False)
    _, p_base = dp_schedule(base)
    picked = p_base[0]
    other = next(c for c in cands if c != picked)
    # tell the oracle the picked candidate's class measures 50x its model
    cal = {"global_ratio": 1.0,
           "classes": {class_key(picked[2], picked[3], picked[4],
                                 picked[0]): {"ratio": 50.0}}}
    skewed = build_cost_oracle(g, specs, candidates=[cands],
                               cache_dir=str(tmp_path), use_cache=False,
                               calibration=cal)
    assert skewed.class_scale == {class_key(picked[2], picked[3],
                                            picked[4], picked[0]): 50.0}
    _, p_skewed = dp_schedule(skewed)
    assert p_skewed[0] == other
    # per-candidate costs moved the way the table says
    assert skewed.node_cost(0, picked) == pytest.approx(
        50.0 * base.node_cost(0, picked))
    assert skewed.node_cost(0, other) == pytest.approx(
        base.node_cost(0, other))


def test_persisted_calibration_feeds_cold_dp(tmp_path):
    """build_cost_oracle auto-loads calibration.json (keyed by this device's
    sig) from the cache dir: the audit's output steers the scheduler with no
    plumbing at the call site; use_calibration=False opts out."""
    from repro.obs.audit import (SCHEMA_CALIBRATION, class_key,
                                 save_calibration)
    g, specs, cands = _flip_setup()
    base = build_cost_oracle(g, specs, candidates=[cands],
                             cache_dir=str(tmp_path), use_cache=False,
                             use_calibration=False)
    _, p_base = dp_schedule(base)
    picked = p_base[0]
    other = next(c for c in cands if c != picked)
    save_calibration({"schema": SCHEMA_CALIBRATION,
                      "device_sig": at.device_sig(),
                      "global_ratio": 1.0,
                      "classes": {class_key(picked[2], picked[3], picked[4],
                                            picked[0]): {"ratio": 50.0}}},
                     str(tmp_path))
    fed = build_cost_oracle(g, specs, candidates=[cands],
                            cache_dir=str(tmp_path), use_cache=False)
    _, p_fed = dp_schedule(fed)
    assert p_fed[0] == other
    # an explicit opt-out restores the uncalibrated schedule
    off = build_cost_oracle(g, specs, candidates=[cands],
                            cache_dir=str(tmp_path), use_cache=False,
                            use_calibration=False)
    _, p_off = dp_schedule(off)
    assert p_off[0] == picked
    # another device's table is never consumed
    save_calibration({"schema": SCHEMA_CALIBRATION,
                      "device_sig": "some-other-device",
                      "global_ratio": 1.0,
                      "classes": {class_key(other[2], other[3], other[4],
                                            other[0]): {"ratio": 500.0}}},
                     str(tmp_path))
    again = build_cost_oracle(g, specs, candidates=[cands],
                              cache_dir=str(tmp_path), use_cache=False)
    assert class_key(other[2], other[3], other[4],
                     other[0]) not in again.class_scale
