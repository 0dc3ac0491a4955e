"""The arxiv GAT (DGL's ogbn-arxiv variant) against the plain reference of
the chip benchmark (``benchmarks/chip/reference_gat.py``) on a seeded small
graph, its head-by-head path against the one-pass path, the edge softmax at
its edges, and ``gat-cora`` against the layer as it was before the arxiv
options.

Tolerances: program and reference are float32 on the CPU and compute the
same sums in other orders (scores as ``h (W a)`` against ``(h W) a``, self
loops analytic against appended edges, heads sliced against whole), so
they differ by float32 rounding: a few units in the 7th digit of the
largest value, through three layers and a softmax.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.families import GNNBundle
from repro.models import gat
from repro.nn.layers import cross_entropy

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402
import reference_gat  # noqa: E402

N, E, D_IN, CLASSES = 240, 1500, 16, 5
ARXIV = {"n_heads": 3, "n_layers": 3, "final_heads": 3, "residual": True,
         "final_bias": True, "activation": "relu", "self_loops": True,
         "norm": True}


def _graph(seed=0, isolated=()):
    """Random edges; nodes in ``isolated`` get no in-edges."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N, E).astype(np.int32)
    dst = rng.integers(0, N, E).astype(np.int32)
    keep = ~np.isin(dst, isolated)
    src, dst = src[keep], dst[keep]
    x = rng.standard_normal((N, D_IN)).astype(np.float32)
    labels = rng.integers(0, CLASSES, N).astype(np.int32)
    mask = rng.random(N) < 0.6
    return src, dst, x, labels, mask


def _batch(src, dst, x, labels, mask):
    ones = np.float32(1.0)
    return {"src": jnp.asarray(src), "dst": jnp.asarray(dst),
            "edge_mask": jnp.ones(src.shape[0], bool),
            "labels": jnp.asarray(labels), "train_mask": jnp.asarray(mask),
            "x": jnp.asarray(x),
            "deg": jnp.asarray(np.bincount(dst, minlength=N) + ones),
            "out_deg": jnp.asarray(np.bincount(src, minlength=N) + ones)}


def _as_reference(params):
    return [{k: (v["w"] if k in ("w", "res") else v) for k, v in p.items()}
            for p in params["layers"]]


def _leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


@pytest.fixture(params=[8, 50], ids=["3x8-one-pass", "3x50-per-head"])
def arxiv(request):
    """The arxiv GAT at 3 heads of ``F``: 3 x 8 (24 wide) runs its hidden
    layers in one pass, 3 x 50 (150 wide) head by head, as 3 x 250 does."""
    bundle = GNNBundle("gat", dict(ARXIV, d_hidden=request.param),
                       n_classes=CLASSES)
    params = bundle.init_params(jax.random.PRNGKey(3), D_IN)
    return bundle, params


def test_program_matches_the_reference(arxiv):
    bundle, params = arxiv
    src, dst, x, labels, mask = _graph(1, isolated=(0, 1, 2))
    batch = _batch(src, dst, x, labels, mask)
    graph = {k: batch[k] for k in ("src", "dst", "edge_mask", "deg",
                                   "out_deg")}
    with jax.default_matmul_precision("float32"):
        got = gat.gat_apply(params, batch["x"], graph, act=jax.nn.relu,
                            self_loops=True, norm=True)
        ref = reference_gat.forward(_as_reference(params), batch["x"],
                                    batch["src"], batch["dst"],
                                    reference.matmul_highest)
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(got, ref, rtol=0, atol=2e-6 * scale)
        g_prog = jax.grad(bundle.loss_fn("full_graph_sm"))(params, batch)
        g_ref = jax.grad(reference_gat.loss)(
            _as_reference(params), batch["x"], batch["src"], batch["dst"],
            batch["labels"], batch["train_mask"], reference.matmul_highest)
    # every leaf, in the same order; a_dst's gradient is a sum of terms
    # that nearly cancel (the destination's score shifts all of its
    # in-edges alike), so each leaf is compared against its own norm
    for a, b in zip(_leaves(g_prog), _leaves(g_ref)):
        assert a.shape == b.shape
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b)


def test_the_head_by_head_path_equals_one_pass(monkeypatch):
    bundle = GNNBundle("gat", dict(ARXIV, d_hidden=50), n_classes=CLASSES)
    params = bundle.init_params(jax.random.PRNGKey(4), D_IN)
    src, dst, x, labels, mask = _graph(2)
    batch = _batch(src, dst, x, labels, mask)
    p = params["layers"][0]

    def layer_sum(p, lanes):
        # 3 x 50 is wider than 128 lanes (head by head) and narrower than
        # 10**6 (one pass)
        monkeypatch.setattr(gat, "LANES", lanes)
        return gat.gat_layer(p, batch["x"], batch, self_loops=True,
                             norm=True)
    with jax.default_matmul_precision("float32"):
        a, b = layer_sum(p, 128), layer_sum(p, 10**6)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-6 * float(jnp.abs(b).max()))
        ga, gb = (jax.grad(lambda q: jnp.sum(layer_sum(q, lanes) ** 2))(p)
                  for lanes in (128, 10**6))
    for u, v in zip(_leaves(ga), _leaves(gb)):
        assert np.linalg.norm(u - v) <= 1e-5 * np.linalg.norm(v)


def test_edge_softmax_with_self_loops_and_masked_edges():
    """Through the layer: node 0 has only its self loop, node 1 two live
    in-edges and a masked one, node 2 one in-edge, masked, so only its self
    loop counts.  The masked edges come from a node whose scores are far
    above the others', so that any weight they got would show."""
    rng = np.random.default_rng(0)
    h = rng.standard_normal((6, 4)).astype(np.float32)
    h[4] *= 30.0
    h[5] *= 30.0
    p = {"w": {"w": jnp.asarray(rng.standard_normal((4, 2)), jnp.float32)},
         "a_src": jnp.asarray([[1.0, 0.5]]), "a_dst": jnp.asarray([[0.3, -0.2]])}
    graph = {"src": jnp.asarray([2, 3, 4, 5], jnp.int32),
             "dst": jnp.asarray([1, 1, 1, 2], jnp.int32),
             "edge_mask": jnp.asarray([True, True, False, False])}
    with jax.default_matmul_precision("float32"):
        got = np.asarray(gat.gat_layer(p, jnp.asarray(h), graph,
                                       self_loops=True))
    z = h.astype(np.float64) @ np.asarray(p["w"]["w"], np.float64)
    s, t = z @ [1.0, 0.5], z @ [0.3, -0.2]
    leaky = lambda v: np.where(v > 0, v, 0.2 * v)
    np.testing.assert_allclose(got[0], z[0], rtol=1e-5)
    np.testing.assert_allclose(got[2], z[2], rtol=1e-5)
    e = leaky(s[[2, 3, 1]] + t[1])
    alpha = np.exp(e - e.max()) / np.exp(e - e.max()).sum()
    np.testing.assert_allclose(got[1], alpha @ z[[2, 3, 1]], rtol=1e-5)
    np.testing.assert_allclose(got[3:], z[3:], rtol=1e-5)


# ------------------------------------------------------ packed attention
def _attention_per_head(s, t, src, dst, mask, n):
    """The attention as it was before its heads were packed: each head's
    scores, segment max and numerators one ``(E,)`` row, with self loops."""
    leaky = lambda v: jax.nn.leaky_relu(v, 0.2)
    ex, ex_self = [], []
    for k in range(s.shape[0]):
        e = jnp.where(mask, leaky(s[k][src] + t[k][dst]), -jnp.inf)
        e_self = leaky(s[k] + t[k])
        mx = jax.lax.stop_gradient(jnp.maximum(
            jax.ops.segment_max(e, dst, num_segments=n), e_self))
        ex.append(jnp.where(mask, jnp.exp(e - mx[dst]), 0.0))
        ex_self.append(jnp.exp(e_self - mx))
    return jnp.stack(ex), jnp.stack(ex_self)


def _layer_per_head(p, h, graph):
    """An arxiv layer (self loops, symmetric norm, residual) with
    :func:`_attention_per_head` and a plain weighted sum per head."""
    n = h.shape[0]
    heads, f = p["a_src"].shape
    src, dst = graph["src"], graph["dst"]
    z = (h @ p["w"]["w"]).reshape(n, heads, f)
    s = jnp.einsum("nhf,hf->hn", z, p["a_src"])
    t = jnp.einsum("nhf,hf->hn", z, p["a_dst"])
    ex, ex_self = _attention_per_head(s, t, src, dst, graph["edge_mask"], n)
    zs = z * jax.lax.rsqrt(graph["out_deg"])[:, None, None]
    out = []
    for k in range(heads):
        num = (jax.ops.segment_sum(ex[k][:, None] * zs[src, k], dst,
                                   num_segments=n)
               + ex_self[k][:, None] * zs[:, k])
        den = jax.ops.segment_sum(ex[k], dst, num_segments=n) + ex_self[k]
        out.append(num / den[:, None] * jnp.sqrt(graph["deg"])[:, None])
    return jnp.concatenate(out, axis=1) + h @ p["res"]["w"]


def test_packed_attention_matches_the_per_head_formula(arxiv):
    """Every layer, on a graph with isolated nodes (0, 1, 2) and masked
    edges, in the one-pass and the head-by-head weighted sums."""
    bundle, params = arxiv
    src, dst, x, labels, mask = _graph(5, isolated=(0, 1, 2))
    batch = _batch(src, dst, x, labels, mask)
    batch["edge_mask"] = jnp.asarray(
        np.random.default_rng(5).random(src.shape[0]) < 0.8)
    h = batch["x"]
    for p in params["layers"]:
        with jax.default_matmul_precision("float32"):
            got = gat.gat_layer(p, h, batch, self_loops=True, norm=True)
            want = _layer_per_head(p, h, batch)
            np.testing.assert_allclose(got, want, rtol=0,
                                       atol=2e-6 * float(jnp.abs(want).max()))
            g_got, g_want = (jax.grad(lambda q: jnp.sum(f(q) ** 2))(p)
                             for f in (lambda q: gat.gat_layer(
                                 q, h, batch, self_loops=True, norm=True),
                                       lambda q: _layer_per_head(q, h, batch)))
        for u, v in zip(_leaves(g_got), _leaves(g_want)):
            assert np.linalg.norm(u - v) <= 1e-5 * np.linalg.norm(v)
        h = jax.nn.relu(want)


# --------------------------------------------------------------- gat-cora
def _cora_layer_before(p, h, src, dst, mask):
    """The GAT layer as it was before the arxiv options (one pass, scores
    from ``z``, (E, H) softmax), kept here as the yardstick."""
    n = h.shape[0]
    heads, d_out = p["a_src"].shape
    z = (h @ p["w"]["w"]).reshape(n, heads, d_out)
    s = jnp.einsum("nhd,hd->nh", z, p["a_src"])
    t = jnp.einsum("nhd,hd->nh", z, p["a_dst"])
    e = jax.nn.leaky_relu(s[src] + t[dst], 0.2)
    e = jnp.where(mask[:, None], e, -jnp.inf)
    mx = jax.ops.segment_max(e, dst, num_segments=n)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    ex = jnp.where(mask[:, None], jnp.exp(e - mx[dst]), 0.0)
    den = jax.ops.segment_sum(ex, dst, num_segments=n)
    alpha = ex / jnp.maximum(den[dst], 1e-9)
    return jax.ops.segment_sum(z[src] * alpha[:, :, None], dst,
                               num_segments=n)


def _cora_before(params, x, src, dst, mask):
    h = x
    for i, p in enumerate(params["layers"]):
        out = _cora_layer_before(p, h, src, dst, mask)
        h = (jax.nn.elu(out.reshape(out.shape[0], -1))
             if i + 1 < len(params["layers"]) else out.mean(axis=1))
    return h


def test_gat_cora_is_unchanged():
    """Same parameters from the same key, and the same logits and gradients
    up to float32 rounding: the scores' dot products are now summed per
    head from ``h (W a)``."""
    from repro.configs.gat_cora import MODEL_KW
    bundle = GNNBundle("gat", MODEL_KW, n_classes=7)
    params = bundle.init_params(jax.random.PRNGKey(5), D_IN)
    assert set(params["layers"][0]) == {"w", "a_src", "a_dst"}
    assert [p["a_src"].shape for p in params["layers"]] == [(8, 8), (1, 7)]
    src, dst, x, labels, mask = _graph(3)
    emask = jnp.asarray(np.random.default_rng(3).random(src.shape[0]) < 0.9)
    graph = {"src": jnp.asarray(src), "dst": jnp.asarray(dst),
             "edge_mask": emask}
    with jax.default_matmul_precision("float32"):
        got = gat.gat_apply(params, jnp.asarray(x), graph)
        want = _cora_before(params, jnp.asarray(x), graph["src"],
                            graph["dst"], emask)
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=2e-6 * float(jnp.abs(want).max()))
        w = jnp.asarray(mask, jnp.float32)
        g_now = jax.grad(lambda p: cross_entropy(
            gat.gat_apply(p, jnp.asarray(x), graph), labels, w))(params)
        g_before = jax.grad(lambda p: cross_entropy(_cora_before(
            p, jnp.asarray(x), graph["src"], graph["dst"], emask),
            labels, w))(params)
    for a, b in zip(_leaves(g_now), _leaves(g_before)):
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b)


# --------------------------------------------------------------- tracing
def test_each_layer_counts_its_edge_passes_and_has_its_scopes():
    bundle = GNNBundle("gat", dict(ARXIV, d_hidden=50), n_classes=CLASSES)
    params = bundle.init_params(jax.random.PRNGKey(6), D_IN)
    batch = _batch(*_graph(4))
    obs.reset()
    with obs.enabled_scope(True):
        hlo = jax.jit(bundle.loss_fn("full_graph_sm")).lower(
            params, batch).compile().as_text()
        counts = {k: v for k, v in obs.snapshot()["counters"].items()
                  if k.startswith("model.gat.edge_passes")}
    obs.reset()
    # hidden layers (150 wide): three passes of one head; last (15): one
    assert counts == {"model.gat.edge_passes{heads=1,layer=0}": 3,
                      "model.gat.edge_passes{heads=1,layer=1}": 3,
                      "model.gat.edge_passes{heads=3,layer=2}": 1}
    paths = {tuple(op.split("/")) for op in re.findall(r'op_name="([^"]*)"',
                                                        hlo)}
    for i in range(3):
        for scope in ("attention", "aggregate", "update"):
            assert any(f"layer{i}" in p and scope in p[p.index(f"layer{i}"):]
                       for p in paths), (i, scope)


def test_gat_arxiv_is_registered_for_the_dry_run():
    from repro.configs import get
    bundle = get("gat-arxiv").bundle()
    assert bundle.model_kw["d_hidden"] == 250 and bundle.n_classes == 40
    assert "out_deg" in bundle.input_specs("full_graph_sm")
    params, _ = bundle.abstract_state("full_graph_sm")
    assert [p["a_src"].shape for p in params["layers"]] == [
        (3, 250), (3, 250), (3, 40)]


def test_attention_is_one_pass_per_edge_op_for_all_heads():
    """The counter reads one pass of 3 heads a layer.  Lowered on the CPU,
    the gradient of each arxiv layer's attention gathers ``(3, E)`` and
    scatters into ``(3, N)``: 3 scatters (the segment max and the two
    scatter-adds of the backward) and 6 gathers (the checkpoint runs the
    forward's 3 again), and no one-row pass; three one-row heads take 9
    and 9."""
    bundle = GNNBundle("gat", dict(ARXIV, d_hidden=50), n_classes=CLASSES)
    params = bundle.init_params(jax.random.PRNGKey(6), D_IN)
    batch = _batch(*_graph(4))
    obs.reset()
    with obs.enabled_scope(True):
        jax.eval_shape(bundle.loss_fn("full_graph_sm"), params, batch)
        counts = {k: v for k, v in obs.snapshot()["counters"].items()
                  if k.startswith("model.gat.attention_passes")}
    obs.reset()
    assert counts == {f"model.gat.attention_passes{{heads=3,layer={i}}}": 1
                      for i in range(3)}

    h = batch["x"]
    n, e = h.shape[0], batch["src"].shape[0]

    def counter(f, arg):
        hlo = jax.jit(jax.grad(f)).lower(arg).as_text(dialect="hlo")
        return lambda kind, shape: len(
            re.findall(rf"= f32\[{shape}\]\S* {kind}\(", hlo))
    for p in params["layers"]:
        packed = counter(lambda q: jnp.sum(gat.gat_layer(
            q, h, batch, self_loops=True, norm=True) ** 2), p)
        assert (packed("gather", f"3,{e}"),
                packed("scatter", f"3,{n}")) == (6, 3)
        assert (packed("gather", e), packed("scatter", n)) == (0, 0)
        h = jnp.zeros((n, p["w"]["w"].shape[1]))
    per_head = counter(lambda s: sum(jnp.sum(a) for a in _attention_per_head(
        s, s, batch["src"], batch["dst"], batch["edge_mask"], n)),
        jnp.ones((3, n)))
    assert (per_head("gather", e), per_head("scatter", n)) == (9, 9)
