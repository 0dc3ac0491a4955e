"""The ``gat-arxiv.train`` and ``gcn-arxiv.serve`` cells: their files, their
metrics, their FLOP count, and ``correct`` on small stand-ins on the CPU.

``gat_checkout`` adds to ``conftest.checkout`` two cells at the new
configurations' widths on smaller graphs: ``gat-tiny.train`` (3 heads of
250, the head-by-head path, on 2,000 nodes and 20,000 edges: on a few
hundred edges one score that crosses LeakyReLU's kink on rounding moves the
gradient past the cell's limits) and ``gcn-tiny3.serve`` (three GCN layers
at 256), each with the traffic and limits of the cell it stands for.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, run_cell

import flops_gat
import harness
import loadgen
import readings
import reference
from run import Timers

# train_edges_per_s (bound 1%) is left out of gat-arxiv.train: its step
# reads 4% slower in one run of three or four on the chip (PERF.md §2)
NEW_E2E = {"gat-arxiv.train": {"setup_s", "step_ms"},
           "gcn-arxiv.serve": {"setup_s", "serve_p50_ms", "serve_p95_ms"}}
NEW_PER_LAYER = {
    "gat-arxiv.train": {"setup.reorder_s", "setup.compile_s", "train.mfu",
                        "device_idle.train"},
    "gcn-arxiv.serve": {"setup.reorder_s", "setup.compile_s",
                        "device_idle.serve", "serve.batch_ms",
                        "serve.hit_rate", "serve.gen_late_ms"}}
TINY = {"gat-tiny.train": ("gat-arxiv.train", "gat-tiny",
                           {"num_nodes": 2000, "num_edges": 20000}),
        "gcn-tiny3.serve": ("gcn-arxiv.serve", "gcn-tiny3s",
                            {"num_nodes": 400, "num_edges": 2400})}


@pytest.fixture
def gat_checkout(checkout):
    spec = harness.load_spec(str(checkout))
    base = checkout / harness.REL
    for name, (like, cfg_name, graph) in TINY.items():
        cell = harness.workload(spec, like)
        cfg = harness.config(spec, cell["config"], REPO)
        cfg["name"] = cfg_name
        cfg["graph"].update(graph)
        rel = f"{harness.REL}/configs/{cfg_name}.json"
        (checkout / rel).write_text(json.dumps(cfg))
        spec["configs"].append({"name": cfg_name, "source": "test",
                                "file": rel, "reduced": [], "why": "test"})
        spec["workloads"].append(dict(cell, name=name, config=cfg_name))
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
        (base / "limits" / f"{name}.json").write_text(json.dumps(
            harness.limits(like)))
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    return checkout


def failed_checks(out):
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


# --------------------------------------------------------------- files
@pytest.mark.parametrize("cell", sorted(NEW_E2E))
def test_the_new_cells_find_their_files(cell):
    spec = harness.load_spec()
    w = harness.workload(spec, cell)
    cfg = harness.config(spec, w["config"])
    assert cfg["name"] == w["config"] and w["chips"] == 1
    drv = harness.driver(harness.traffic(w["traffic"])["driver"])
    for name in ("Driver", "readings", "KIND", "HOST_SPANS"):
        assert hasattr(drv, name)
    assert drv.KIND == cell.rsplit(".", 1)[1]
    limits = harness.limits(cell)
    assert limits and all(v >= 0 for v in limits.values())


@pytest.mark.parametrize("cell", sorted(NEW_E2E))
def test_the_new_cells_report_exactly_their_metrics(cell):
    spec = harness.load_spec()
    got = {m["name"] for m in harness.cell_metrics(spec, cell, "end_to_end")}
    assert got == NEW_E2E[cell]
    per_layer = harness.cell_metrics(spec, cell, "per_layer")
    assert {m["name"] for m in per_layer} == NEW_PER_LAYER[cell]
    assert all(m["moves"] in got for m in per_layer)


def test_gat_arxiv_is_at_its_published_widths():
    spec = harness.load_spec()
    cfg = harness.config(spec, "gat-arxiv")
    assert (cfg["layers"], cfg["heads"], cfg["hidden_per_head"],
            cfg["final_heads"], cfg["classes"]) == (3, 3, 250, 3, 40)
    entry = [c for c in spec["configs"] if c["name"] == "gat-arxiv"][0]
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert all(k in cfg for k in cfg["reduced"])
    assert cfg["graph"] == harness.config(spec, "gcn-arxiv")["graph"]


# --------------------------------------------------------------- flops
def test_gat_flops_on_a_hand_counted_shape():
    # N = 2, E = 3 (E' = 5), d_in = 4; layer 0: 2 heads x 3, layer 1: 1 x 2
    # layer 0 (HF 6): proj 2*(2*2*4*6) = 192, x2 = 384; dots 12*2*6 = 144;
    #   sums 6*5*6 = 180; scores and softmax 4*5*2 = 40          -> 748
    # layer 1 (d_in 6, HF 2): proj 2*(2*2*6*2) = 96, x3 = 288; dots
    #   12*2*2 = 48; sums 6*5*2 = 60; scores and softmax 4*5*1 = 20 -> 416
    assert flops_gat.gat_train_step(2, 3, 4, [2, 1], [3, 2]) == 748 + 416


def test_gat_arxiv_flops_are_about_one_and_a_half_teraflop():
    spec = harness.load_spec()
    cfg = harness.config(spec, "gat-arxiv")
    g = cfg["graph"]
    drv = harness.driver("full_graph_train_gat")
    heads, widths = drv.geometry(cfg)
    total = flops_gat.gat_train_step(g["num_nodes"], g["num_edges"],
                                     g["feat_dim"], heads, widths)
    assert 1.4e12 < total < 1.6e12


# --------------------------------------------------------------- correct
def test_a_sound_gat_run_is_correct(gat_checkout):
    out = run_cell(gat_checkout, "gat-tiny.train")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compiles_in_window"] == 0
    assert set(out["metrics"]) == NEW_E2E["gat-arxiv.train"]


def test_half_the_gat_batch_left_out_is_caught(gat_checkout, monkeypatch):
    from repro.configs.families import GNNBundle
    real = GNNBundle.loss_fn

    def half(self, shape, executor="segment", exec_plan=None):
        loss = real(self, shape, executor, exec_plan)

        def on_half(params, batch):
            mask = batch["train_mask"]
            keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
            return loss(params, dict(batch, train_mask=mask & keep))
        return on_half
    monkeypatch.setattr(GNNBundle, "loss_fn", half)
    out = run_cell(gat_checkout, "gat-tiny.train")
    assert not out["correct"]
    assert "loss_gap" in failed_checks(out)


def _driver(root, cell):
    spec = harness.load_spec(str(root))
    w = harness.workload(spec, cell)
    traffic = harness.traffic(w["traffic"], str(root))
    drv = harness.driver(traffic["driver"])
    cfg = harness.config(spec, w["config"], str(root))
    return drv, drv.Driver(cfg, traffic, 11, Timers()), harness.limits(
        cell, str(root))


def test_the_gat_control_and_fault_fail(gat_checkout):
    harness.add_program_path()
    with jax.default_matmul_precision("float32"):
        drv, d, limits = _driver(gat_checkout, "gat-tiny.train")
    d.release()
    sound = drv.readings(d.checked_losses, d.first_m, d.params0,
                         d.params_after, *d.reference_steps())
    assert all(sound[k] <= limits[k] for k in limits), (sound, limits)
    ctrl = readings.train_control(drv, d)
    assert any(ctrl[k] > limits[k] for k in limits), (ctrl, limits)
    half = readings.train_control(drv, d, reference.matmul_highest,
                                  readings.half_mask(d, 11))
    assert any(half[k] > limits[k] for k in limits), (half, limits)


def test_a_program_without_the_arxiv_options_stops_at_once(
        gat_checkout, monkeypatch):
    """A program whose GAT ignores the configuration's residual, final
    heads and bias is refused before the graph is built."""
    from repro.configs.families import GNNBundle
    from repro.models import gat_init
    import graphs

    def plain(self, key, d_feat):
        kw = self.model_kw
        return gat_init(key, d_feat, kw["d_hidden"], kw["n_heads"],
                        self.n_classes, kw["n_layers"])
    monkeypatch.setattr(GNNBundle, "init_params", plain)
    monkeypatch.setattr(graphs, "build", lambda spec: pytest.fail(
        "the graph was built"))
    harness.add_program_path()
    with pytest.raises(harness.BenchError, match="does not build"):
        _driver(gat_checkout, "gat-tiny.train")


def test_the_gat_weights_come_from_the_seed_not_the_program(
        gat_checkout, monkeypatch):
    """Both sides start from weights the benchmark draws: a program whose
    own initial values are degenerate (all zero) changes nothing of them."""
    from repro.configs.families import GNNBundle
    import reference_gat
    real = GNNBundle.init_params
    monkeypatch.setattr(GNNBundle, "init_params", lambda self, key, d: (
        jax.tree_util.tree_map(jnp.zeros_like, real(self, key, d))))
    harness.add_program_path()
    with jax.default_matmul_precision("float32"):
        drv, d, _ = _driver(gat_checkout, "gat-tiny.train")
    d.release()
    cfg = d.cfg
    heads, widths = drv.geometry(cfg)
    want = reference_gat.init(jax.random.fold_in(harness.seed_key(11), 1),
                              cfg["graph"]["feat_dim"], tuple(heads),
                              tuple(widths))
    got = jax.tree_util.tree_leaves(d.params0)
    assert len(got) == len(jax.tree_util.tree_leaves(want))
    for a, b in zip(got, jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    for layer in d.params0:            # N(0, 1/d_in) and N(0, 0.01)
        d_in = layer["w"].shape[0]
        for k, sd in (("w", d_in ** -0.5), ("res", d_in ** -0.5),
                      ("a_src", 0.1), ("a_dst", 0.1)):
            assert abs(np.std(layer[k]) / sd - 1) < 0.25, k


def test_a_sound_deep_serving_run_is_correct(gat_checkout):
    out = run_cell(gat_checkout, "gcn-tiny3.serve")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    assert set(out["metrics"]) == NEW_E2E["gcn-arxiv.serve"]


# --------------------------------------------------------------- serving
def test_the_deep_traced_window_asks_for_the_next_nodes(gat_checkout):
    """The traced window's requests are the picks that follow the measured
    window's in the traffic's stream, not a replay of its first ones."""
    import contextlib
    harness.add_program_path()
    with jax.default_matmul_precision("float32"):
        drv, d, _ = _driver(gat_checkout, "gcn-tiny3.serve")
        d.window(2.0)
        n = len(d.run["t"])
        seen = []
        real = d._open_loop

        def record(sched, annotate=None):
            seen.append(sched.nodes.copy())
            return real(sched, annotate)
        d._open_loop = record
        d.traced_window(1.0, lambda name: contextlib.nullcontext())
    t = d.traffic
    m = len(seen[0])
    stream = loadgen.zipf_poisson(d.data.num_nodes, t["rate"],
                                  (n + m) / t["rate"], t["zipf_a"],
                                  t["popularity_seed"], 0).nodes
    window = loadgen.zipf_poisson(d.data.num_nodes, t["rate"],
                                  n / t["rate"], t["zipf_a"],
                                  t["popularity_seed"], 0).nodes
    # the window's picks and the traced ones make up the longer stream
    assert sorted(np.concatenate([window, seen[0]])) == sorted(stream)
    assert sorted(window) == sorted(d.run["nodes"])
    assert d._skip == 0


def test_a_three_layer_session_serves_the_reference_rows():
    """``GNNSession`` at two hidden widths answers every node with the row
    of ``reference.forward`` (the float32 GCN of the benchmark)."""
    harness.add_program_path()
    from repro.graph import Graph
    from repro.serve import MicroBatcher, ServeEngine, make_session
    from repro.serve.batcher import MicroBatch, Request
    from repro.serve.cache import EmbeddingCache
    import graphs
    import inputs

    data = graphs.community_power_law(300, 1800, 24, 5, seed=4)
    dims = (24, 32, 16, 5)
    x, params = inputs.make(harness.seed_key(9), jnp.asarray(data.labels),
                            dims)
    g = Graph(src=data.src, dst=data.dst, num_nodes=data.num_nodes,
              node_feat=np.asarray(x), labels=data.labels,
              train_mask=data.train_mask)
    sess = make_session("gcn", g, hidden=[32, 16], out_dim=5,
                        executor="segment")
    assert sess.num_layers == 3 and sess.layer_dims == list(dims)
    sess.params = params
    engine = ServeEngine(sess, EmbeddingCache(sess.layer_dims, 4096,
                                              num_nodes=data.num_nodes),
                         MicroBatcher(max_batch=8, max_wait=1e-3),
                         oracle_check=False)
    got = []
    for i in range(0, data.num_nodes, 4):         # 300 nodes, batches of 4
        ids = np.arange(i, i + 4, dtype=np.int32)
        got.append(engine.process_batch(MicroBatch(
            [Request(int(n), int(n), 0.0) for n in ids], ids,
            np.ones(4, bool), 0.0, "full", np.zeros(4))))
    got = np.concatenate(got)
    with jax.default_matmul_precision("float32"):
        ref = np.asarray(reference.forward_jit(
            inputs.as_reference(params), x, jnp.asarray(data.src),
            jnp.asarray(data.dst), matmul=reference.matmul_highest))
    np.testing.assert_allclose(got, ref, rtol=0,
                               atol=1e-5 * np.abs(ref).max())


# --------------------------------------------------------------- tracing
def test_attention_ms_reads_the_attention_scopes():
    read = harness.metric_reader("train.attention_ms")
    trace = {"device_scopes": {"layer0/attention": 0.030,
                               "layer1/attention": 0.010,
                               "layer0/aggregate": 0.200,
                               "layer0/while/body/checkpoint/aggregate": 0.1,
                               "layer1/update": 0.005, "": 0.001},
             "harness_spans": {"train.dispatch": 2}}
    assert read({"kind": "train", "trace": trace}) == pytest.approx(20.0)
    gcn = dict(trace, device_scopes={"layer0/aggregate": 0.2})
    assert read({"kind": "train", "trace": gcn}) is None
    assert read({"kind": "serve", "trace": trace}) is None
    assert read({"kind": "train", "trace": None}) is None
