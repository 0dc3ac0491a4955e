"""Per-layer computation order in GCN's non-fused loop.

``gcn_apply`` runs each layer aggregate-first or update-first, whichever
``repro.exec.plan.choose_order`` picks from its widths.  Every executor of
that loop must give the aggregate-first result and gradients, aggregate at
the width the verdict names, and count the verdict once per layer."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import build_blockell, build_shared_plan
from repro.exec.plan import choose_order
from repro.graph import Graph
from repro.models.gcn import gcn_apply, gcn_init, gcn_loss, make_graph_inputs
from repro.nn.layers import cross_entropy

N = 160
HI = jax.lax.Precision.HIGHEST

DIMS = {
    "shrinking": [24, 10, 3],
    "growing": [3, 10, 24],
    "equal": [12, 12, 12],
    "arxiv-like": [8, 16, 16, 5],    # grow, tie, shrink
}
EXECUTORS = ["segment", "shared", "blockell"]


@pytest.fixture(scope="module")
def case():
    """A graph with very uneven in-degrees (a few hubs, many leaves), no
    duplicate edges, and a dense ``A_hat`` written out from its edges."""
    rng = np.random.default_rng(3)
    hubs = rng.integers(0, N, 6)
    dst = np.concatenate([np.repeat(hubs, 40), rng.integers(0, N, 500)])
    src = rng.integers(0, N, dst.shape[0])
    keep = src != dst
    pairs = np.unique(np.stack([src[keep], dst[keep]], 1), axis=0)
    g = Graph(src=pairs[:, 0].astype(np.int32),
              dst=pairs[:, 1].astype(np.int32), num_nodes=N)
    a = np.eye(N, dtype=np.float64)
    np.add.at(a, (g.dst, g.src), 1.0)
    d = a.sum(1)
    assert d.max() > 10 * np.median(d)
    ell = build_blockell(g, bm=64, bk=64)
    return {
        "graph": make_graph_inputs(g),
        "a_hat": jnp.asarray(a / np.sqrt(d)[:, None] / np.sqrt(d)[None, :],
                             jnp.float32),
        "kw": {"segment": {},
               "shared": {"plan": build_shared_plan(g)},
               "blockell": {"ell": {"block_cols": jnp.asarray(ell.block_cols),
                                    "blocks": jnp.asarray(ell.blocks),
                                    "bm": ell.bm, "bk": ell.bk}}},
        "labels": jnp.asarray(rng.integers(0, 3, N)),
        "mask": jnp.asarray(rng.random(N) < 0.5),
    }


def _inputs(case, dims):
    rng = np.random.default_rng(len(dims) * 100 + dims[0])
    params = gcn_init(jax.random.PRNGKey(dims[1]), dims)
    for p in params["layers"]:     # a bias through A_hat would show
        p["b"] = jnp.asarray(rng.standard_normal(p["b"].shape), jnp.float32)
    x = jnp.asarray(rng.standard_normal((N, dims[0])), jnp.float32)
    return params, x


def _reference_apply(params, x, a_hat):
    """act(A_hat h W + b), aggregate-first, with a dense ``A_hat``."""
    h = x
    layers = params["layers"]
    for i, p in enumerate(layers):
        h = jnp.dot(jnp.dot(a_hat, h, precision=HI), p["w"], precision=HI)
        h = h + p["b"]
        if i + 1 < len(layers):
            h = jax.nn.relu(h)
    return h


def _reference_loss(params, x, a_hat, labels, mask):
    return cross_entropy(_reference_apply(params, x, a_hat), labels,
                         mask.astype(jnp.float32))


def _widths(jaxpr, pick):
    """``pick(eqn)`` over every equation, through nested jaxprs, in program
    order, where it is not None."""
    out = []
    for eqn in jaxpr.eqns:
        w = pick(eqn)
        if w is not None:
            out.append(w)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out.extend(_widths(sub.jaxpr, pick))
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out.extend(_widths(sub, pick))
    return out


def _scatter_width(eqn):
    """Feature width of a scatter-add's (``segment_sum``'s) updates."""
    if eqn.primitive.name == "scatter-add":
        return eqn.invars[2].aval.shape[-1]
    return None


def _blockell_width(eqn):
    """Feature width of a block-ELL tile product: the batched dot_general
    over the gathered ``(W, bk, d)`` tiles."""
    if (eqn.primitive.name == "dot_general"
            and eqn.params["dimension_numbers"][1][0]):
        return eqn.invars[1].aval.shape[-1]
    return None


def _expected_widths(dims, e):
    return [b if choose_order(N, e, a, b) == "update_first" else a
            for a, b in zip(dims[:-1], dims[1:])]


@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("dims", list(DIMS.values()), ids=list(DIMS))
def test_order_matches_aggregate_first(case, dims, executor):
    """Forward and gradients (params and x) equal the aggregate-first
    computation to 1e-5, non-zero biases on an irregular graph."""
    params, x = _inputs(case, dims)
    graph, kw, a_hat = case["graph"], case["kw"][executor], case["a_hat"]
    labels, mask = case["labels"] % dims[-1], case["mask"]

    got = gcn_apply(params, x, graph, executor=executor, **kw)
    want = _reference_apply(params, x, a_hat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)

    g_got = jax.grad(gcn_loss, argnums=(0, 1))(
        params, x, graph, labels, mask, executor=executor, **kw)
    g_want = jax.grad(_reference_loss, argnums=(0, 1))(
        params, x, a_hat, labels, mask)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5),
        g_got, g_want)


@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("dims", list(DIMS.values()), ids=list(DIMS))
def test_order_aggregation_width(case, dims, executor):
    """The traced forward aggregates each layer at ``choose_order``'s width:
    ``d_out`` update-first, ``d_in`` aggregate-first."""
    params, x = _inputs(case, dims)
    graph, kw = case["graph"], case["kw"][executor]
    want = _expected_widths(dims, int(graph["src"].shape[0]))
    jaxpr = jax.make_jaxpr(
        lambda p, x: gcn_apply(p, x, graph, executor=executor, **kw)
    )(params, x).jaxpr
    if executor == "blockell":
        assert _widths(jaxpr, _blockell_width) == want
        return
    widths = _widths(jaxpr, _scatter_width)
    per_layer = len(widths) // len(want)
    assert per_layer >= 1 and len(widths) == per_layer * len(want)
    assert widths == [w for w in want for _ in range(per_layer)]


@pytest.mark.parametrize("dims", list(DIMS.values()), ids=list(DIMS))
def test_order_counter(case, dims):
    """``model.gcn.order`` counts one verdict per layer per trace while obs
    is enabled, and nothing while it is disabled."""
    params, x = _inputs(case, dims)
    graph = case["graph"]
    verdicts = [choose_order(N, int(graph["src"].shape[0]), a, b)
                for a, b in zip(dims[:-1], dims[1:])]
    names = ("aggregate_first", "update_first")

    def counts():
        return {o: obs.counter("model.gcn.order", order=o).value
                for o in names}

    def trace():
        jax.make_jaxpr(lambda p, x: gcn_apply(p, x, graph))(params, x)

    before = counts()
    with obs.enabled_scope(False):
        trace()
    assert counts() == before
    with obs.enabled_scope():
        trace()
    after = counts()
    assert {o: after[o] - before[o] for o in names} == {
        o: verdicts.count(o) for o in names}
