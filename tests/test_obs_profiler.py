"""repro.obs on the profiler's clock: spans as ``jax.profiler`` annotations
while enabled, ``host.gc`` spans, name scopes in the compiled training step,
and the serving path's queue-wait histogram and batch span."""
import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.serve import MicroBatcher, Request, ServeEngine


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    obs.disable()
    obs.stop_trace()
    yield
    obs.reset()
    obs.disable()
    obs.stop_trace()


def _profile(tmp_path, fn):
    """Run ``fn`` inside a CPU ``jax.profiler`` trace; returns the trace's
    host-plane events as (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_an_enabled_span_encloses_its_ops_in_the_profiler_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()

    def work():
        with obs.span("t.outer", cat="test", profile_args=True,
                      k=1) as sp:
            with obs.span("t.quiet", cat="test", hidden=2):
                f(x).block_until_ready()
            sp.set(verdict="ok")

    obs.enable()
    events = _profile(tmp_path, work)
    obs.disable()
    (outer,) = [e for e in events if e[0] == "t.outer"]
    (quiet,) = [e for e in events if e[0] == "t.quiet"]
    assert outer[3] == {"cat": "test", "k": 1, "verdict": "ok"}
    assert quiet[3] == {"cat": "test"}          # args only where asked for
    ops = [e for e in events if e[3].get("hlo_module", "").startswith("jit")]
    assert ops, "no CPU op events in the trace"
    assert outer[1] <= quiet[1] <= min(e[1] for e in ops)
    assert max(e[2] for e in ops) <= quiet[2] <= outer[2]


def test_a_disabled_span_is_the_shared_noop():
    assert obs.span("a", cat="t", profile_args=True, cpu=True,
                    x=1) is obs.NOOP_SPAN
    obs.enable()
    assert obs.span("a") is not obs.NOOP_SPAN
    obs.disable()
    assert obs.span("a") is obs.NOOP_SPAN
    with obs.enabled_scope():
        assert obs.span("a") is not obs.NOOP_SPAN
    assert obs.span("a") is obs.NOOP_SPAN


def test_a_cpu_span_records_its_thread_cpu_time():
    obs.start_trace()
    with obs.span("t.busy", cpu=True):
        sum(i * i for i in range(200000))
    (ev,) = obs.stop_trace()["traceEvents"][1:]
    assert 0 < ev["args"]["cpu_ms"] <= ev["dur"] / 1e3 * 1.05 + 0.5


def test_collections_are_host_gc_spans_only_while_enabled():
    obs.start_trace()
    obs.enable()
    gc.collect()
    n_on = sum(e["name"] == "host.gc"
               for e in obs.current_tracer().events)
    obs.disable()
    gc.collect()
    doc = obs.stop_trace()
    gcs = [e for e in doc["traceEvents"] if e["name"] == "host.gc"]
    assert n_on >= 1 and len(gcs) == n_on
    assert any(e["args"]["generation"] == 2 for e in gcs)
    assert all("collected" in e["args"] for e in gcs)


# ------------------------------------------------------- device name scopes
def _train_step_hlo(executor: str) -> str:
    from repro.configs.families import GNNBundle
    from repro.exec import build_layer_plan
    from repro.graph.datasets import DatasetSpec, synthesize
    from repro.models.gcn import gcn_init
    from repro.train.loop import make_train_step
    from repro.train.optimizer import adam

    g = synthesize(DatasetSpec("t", 200, 800, 12, 3, seed=0))
    plans = None
    if executor == "coo":
        plans = [build_layer_plan(g, "gcn", d_in=12, d_out=8, backend="coo",
                                  order="update_first"),
                 build_layer_plan(g, "gcn", d_in=8, d_out=3, backend="coo",
                                  order="aggregate_first")]
    bundle = GNNBundle("gcn", {"hidden": [8]}, n_classes=3)
    loss = bundle.loss_fn("full_graph_sm",
                          executor="fused" if plans else "segment",
                          exec_plan=plans)
    opt = adam(0.01)
    params = gcn_init(jax.random.PRNGKey(0), [12, 8, 3])
    batch = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst),
             "edge_mask": jnp.ones(g.num_edges, bool),
             "labels": jnp.asarray(g.labels % 3),
             "train_mask": jnp.asarray(g.train_mask),
             "x": jnp.asarray(g.node_feat),
             "deg": jnp.asarray(g.in_degrees().astype(np.float32) + 1.0)}
    step = make_train_step(loss, opt)
    return step.lower(params, opt.init(params), batch).compile().as_text()


@pytest.mark.parametrize("executor", ["segment", "coo"])
def test_the_train_step_scopes_each_layers_aggregation(executor):
    hlo = _train_step_hlo(executor)
    scatter_scopes = re.findall(r'scatter[^\n]*op_name="([^"]*)"', hlo)
    for i in (0, 1):
        assert any(re.search(rf"layer{i}\)*/aggregate/", s)
                   for s in scatter_scopes), (i, scatter_scopes)
    # the transpose of the last layer's aggregation keeps the scope
    assert any(re.search(r"transpose\(jvp\(layer1\)\)/aggregate/", s)
               for s in scatter_scopes)
    assert re.search(r'op_name="[^"]*layer0\)*/update/dot_general', hlo)
    assert 'op_name="jit(step)/optimizer/' in hlo


# ------------------------------------------------------------ serving path
class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _LeafSession:
    """A leaf-only session: the engine's path without a graph."""
    num_layers = 0
    layer_dims = [2]

    def gather(self, ids):
        return np.zeros((len(ids), 2), np.float32)

    oracle = gather


def _serve_one_batch(clock):
    batcher = MicroBatcher(max_batch=4, max_wait=1.0, clock=clock)
    engine = ServeEngine(_LeafSession(), None, batcher, oracle_check=False)
    mb = None
    for i, t in enumerate((0.0, 0.25, 0.5, 1.0)):
        clock.t = t
        mb = batcher.submit(Request(i, i, t))
    clock.t = 1.5
    engine.process_batch(mb)
    return engine


def test_queue_wait_is_batch_start_minus_submit_on_the_batchers_clock():
    obs.enable()
    _serve_one_batch(_Clock())
    h = obs.snapshot()["histograms"]["serve.queue_seconds"]
    assert h["count"] == 4
    assert h["min"] == pytest.approx(0.5) and h["max"] == pytest.approx(1.5)
    assert h["sum"] == pytest.approx(1.5 + 1.25 + 1.0 + 0.5)
    assert 1.0 / 1.03 <= h["p50"] <= 1.25 * 1.03


def test_queue_wait_is_not_recorded_while_disabled():
    _serve_one_batch(_Clock())
    snap = obs.snapshot()["histograms"]
    assert snap.get("serve.queue_seconds", {"count": 0})["count"] == 0


def test_a_batch_span_names_its_requests_and_nothing_per_request():
    obs.start_trace()
    obs.enable()
    _serve_one_batch(_Clock())
    events = obs.stop_trace()["traceEvents"]
    (batch,) = [e for e in events if e["name"] == "serve.batch"]
    assert {k: batch["args"][k] for k in ("seq", "first", "last")} == {
        "seq": 0, "first": 0, "last": 3}
    assert batch["args"]["cpu_ms"] >= 0
    names = {e["name"] for e in events}
    assert not names & {"serve.request", "serve.dedupe"}
    assert "serve.batch_wall_ms" not in obs.snapshot()["gauges"]
