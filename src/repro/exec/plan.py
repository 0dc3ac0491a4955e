"""GraphExecutionPlan — compile a Graph once, aggregate fast forever after.

The plan owns both directions of the aggregation linear map

    F(x)   = s_out ⊙ (A (s_in ⊙ x) [+ s_in ⊙ x])         (forward)
    F*(g)  = s_in ⊙ (Aᵀ (s_out ⊙ g) [+ s_out ⊙ g])       (VJP wrt x)

where A is the (masked, unweighted unless ``weighted=True``) adjacency and
the bracketed term is the analytic self-loop.  Because F is linear, its VJP
is the same fused op with Aᵀ and the scales swapped — so the backward pass
runs through a *precompiled transpose block-ELL plan* instead of letting JAX
transpose a gather/scatter graph.  ``jax.custom_vjp`` wires that in; both
directions share one code path (``_run_side``).

Modes (what s_in / s_out / the diagonal mean):

    "gcn"  : s_in = s_out = rsqrt(deg + 1), diagonal ON — exactly
             D^-1/2 (A + I) D^-1/2 x, the whole GCN ``_aggregate``.
    "sum"  : s = 1, diagonal OFF — plain A x (GIN).
    "mean" : s_in = 1, s_out = 1/max(deg, 1), diagonal OFF (GraphSAGE).

Backends:

    "pallas" : the block-ELL TPU kernels (kernels/spmm_blockell.py) —
               compacted (grid = n_active) or padded (grid = R*W).
    "jnp"    : batched dense-tile einsum over the same block structure —
               portable, differentiable-by-construction, used for parity.
    "coo"    : one segment-sum over dst-sorted edges whose weights pre-fold
               normalization, edge mask, and self-loop — the fastest CPU
               executor (no padded control steps, no elementwise pre/post).

Rows whose destination block has no active slot are never visited by the
compacted Pallas grid; the plan patches them with the analytic diagonal
fallback outside the kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..chaos import inject as chaos
from ..graph.structure import Graph
from ..core.blocksparse import (BlockEll, build_blockell, build_blockell_coo,
                                transpose_graph, traffic_model)
from ..kernels.spmm_blockell import (spmm_blockell_fused,
                                     spmm_blockell_compact,
                                     spmm_blockell_update,
                                     spmm_blockell_update_compact)
from .bucketing import assign_buckets, bucket_occupancy, parse_bucket_sig

MODES = ("gcn", "sum", "mean")
BACKENDS = ("pallas", "jnp", "coo")
ORDERS = ("aggregate_first", "update_first")


class SideMeta(NamedTuple):
    """Static (hashable) facts one direction of the plan needs at trace time."""
    backend: str
    compact: bool
    add_diag: bool
    bm: int
    bk: int
    R: int
    C: int
    n_active: int
    n: int            # num_nodes
    interpret: bool


class BucketMeta(NamedTuple):
    """Static geometry of ONE degree bucket's rectangular block-ELL."""
    bm: int
    bk: int
    R: int            # ceil(n_rows / bm)  (bucket-local destination blocks)
    C: int            # ceil(n / bk)       (global source blocks)
    W: int            # ELL width of this bucket
    n_active: int
    n_rows: int       # nodes assigned to this bucket


class BucketedSideMeta(NamedTuple):
    """Trace-time facts for one direction of a degree-bucketed plan.

    Forward and backward carry INDEPENDENT bucket tuples: the transpose
    graph is re-bucketed by its own in-degrees (= the original graph's
    out-degrees), so each direction's hubs get their own sub-grid — the
    per-bucket transpose plans of ISSUE 9.
    """
    backend: str
    compact: bool
    add_diag: bool
    n: int            # num_nodes
    interpret: bool
    buckets: tuple    # Tuple[BucketMeta, ...]


# ---------------------------------------------------------------------------
# one direction of the fused op, on any backend
# ---------------------------------------------------------------------------
def _run_side(meta, a: Dict[str, jax.Array], x: jax.Array
              ) -> jax.Array:
    if isinstance(meta, BucketedSideMeta):
        return _run_bucketed(meta, a, x)
    if meta.backend == "coo":
        y = jax.ops.segment_sum(x[a["src"]] * a["w"][:, None], a["dst"],
                                num_segments=meta.n)
        if meta.add_diag:
            # self-loop as an elementwise FMA (s_out*s_in per node) — far
            # cheaper than scattering N extra diagonal edges
            y = y + a["dvec"][:, None] * x
        return y
    if meta.backend == "jnp":
        return _jnp_blocks(meta, a, x)
    if meta.backend == "pallas":
        return _pallas_blocks(meta, a, x)
    raise ValueError(meta.backend)


def _jnp_blocks(meta: SideMeta, a: Dict[str, jax.Array], x: jax.Array
                ) -> jax.Array:
    n, d = x.shape
    bm, bk, R, C = meta.bm, meta.bk, meta.R, meta.C
    xs = x * a["s_in"][:, None]
    xb = jnp.pad(xs, ((0, C * bk - n), (0, 0))).reshape(C, bk, d)
    if meta.compact:
        if meta.n_active:
            tiles = xb[a["cols"]]                          # (n_active, bk, d)
            prod = jnp.einsum("abk,akd->abd", a["blocks"], tiles)
            y = jax.ops.segment_sum(prod, a["rows"], num_segments=R)
            y = y.reshape(R * bm, d)[:n]
        else:
            y = jnp.zeros_like(xs)
    else:
        cols = a["block_cols"]
        tiles = xb[jnp.maximum(cols, 0)]                   # (R, W, bk, d)
        tiles = jnp.where((cols >= 0)[:, :, None, None], tiles, 0.0)
        y = jnp.einsum("rwmk,rwkd->rmd", a["blocks"], tiles)
        y = y.reshape(R * bm, d)[:n]
    if meta.add_diag:
        y = y + xs
    return y * a["s_out"][:, None]


def _pallas_blocks(meta: SideMeta, a: Dict[str, jax.Array], x: jax.Array
                   ) -> jax.Array:
    chaos.fail_point("exec.pallas_launch")   # no-op unless a drill armed it
    n, d = x.shape
    bm, bk, R, C = meta.bm, meta.bk, meta.R, meta.C
    dp = -(-d // 128) * 128
    xp = jnp.pad(x, ((0, C * bk - n), (0, dp - d)))
    if meta.compact:
        if meta.n_active == 0:
            y = None
        else:
            y = spmm_blockell_compact(
                a["rows"], a["cols"], a["blocks"], xp,
                a["s_in2d"], a["s_out2d"], bm=bm, bk=bk, n_row_blocks=R,
                add_diag=meta.add_diag, interpret=meta.interpret)
        # destination blocks with no active slot were never written: patch
        # with the analytic diagonal term (zero when there is no self-loop)
        fb = (x * a["s_in"][:, None] * a["s_out"][:, None] if meta.add_diag
              else jnp.zeros_like(x))
        if y is None:
            return chaos.mangle("exec.kernel_result", fb)
        return chaos.mangle("exec.kernel_result",
                            jnp.where(a["node_active"][:, None],
                                      y[:n, :d], fb))
    y = spmm_blockell_fused(
        a["block_cols"], a["blocks"], xp, a["s_in2d"], a["s_out2d"],
        bm=bm, bk=bk, add_diag=meta.add_diag, interpret=meta.interpret)
    return chaos.mangle("exec.kernel_result", y[:n, :d])


# ---------------------------------------------------------------------------
# degree-bucketed multi-grid execution (ISSUE 9)
# ---------------------------------------------------------------------------
def _jnp_bucket(bmeta: BucketMeta, ab: Dict[str, jax.Array], xs: jax.Array,
                add_diag: bool) -> jax.Array:
    """One bucket of the jnp path: a per-bucket PADDED dense-tile einsum.

    ``xs = s_in ⊙ x`` (global).  The per-bucket widths keep the padded grid
    small (hub slots never inflate the tail bucket's W), and the einsum form
    avoids the segment-sum scatter that made the single-grid compact jnp
    path lose to padded on Cora (the PR 3 anomaly)."""
    n, d = xs.shape
    bm, bk, C, R = bmeta.bm, bmeta.bk, bmeta.C, bmeta.R
    xb = jnp.pad(xs, ((0, C * bk - n), (0, 0))).reshape(C, bk, d)
    cols = ab["block_cols"]
    tiles = xb[jnp.maximum(cols, 0)]                       # (R, W, bk, d)
    tiles = jnp.where((cols >= 0)[:, :, None, None], tiles, 0.0)
    y = jnp.einsum("rwmk,rwkd->rmd", ab["blocks"], tiles)
    y = y.reshape(R * bm, d)[:bmeta.n_rows]
    if add_diag:
        y = y + xs[ab["idx"]]
    return y * ab["s_out_sel"][:, None]


def _pallas_bucket(meta: BucketedSideMeta, bmeta: BucketMeta,
                   ab: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """One bucket of the pallas path: a compact sub-grid at this bucket's
    tile, with the self-term operands gathered into bucket-local row order
    (``x_diag`` / ``s_in_diag``) so a single identity bucket is bit-identical
    to the unbucketed compact kernel."""
    n, d = x.shape
    bm, bk, R, C = bmeta.bm, bmeta.bk, bmeta.R, bmeta.C
    if bmeta.n_active == 0:
        # no active slots: every row of this bucket takes the global
        # diagonal fallback (node_active is False for all of them)
        return jnp.zeros((bmeta.n_rows, d), x.dtype)
    dp = _pad128(d)
    xp = jnp.pad(x, ((0, C * bk - n), (0, dp - d)))
    xd = sind = None
    if meta.add_diag:
        xd = jnp.pad(x[ab["idx"]],
                     ((0, R * bm - bmeta.n_rows), (0, dp - d)))
        sind = ab["s_in_diag2d"]
    y = spmm_blockell_compact(
        ab["rows"], ab["cols"], ab["blocks"], xp, ab["s_in2d"],
        ab["s_out2d"], xd, sind, bm=bm, bk=bk, n_row_blocks=R,
        add_diag=meta.add_diag, interpret=meta.interpret)
    return y[:bmeta.n_rows, :d]


def _run_bucketed(meta: BucketedSideMeta, a: Dict[str, jax.Array],
                  x: jax.Array) -> jax.Array:
    """Multi-grid aggregation: one launch per degree bucket, outputs stitched
    back to original node order through the precomputed inverse permutation."""
    n, d = x.shape
    if meta.backend == "jnp":
        xs = x * a["s_in"][:, None]
        outs = [_jnp_bucket(bmeta, ab, xs, meta.add_diag)
                for bmeta, ab in zip(meta.buckets, a["buckets"])
                if bmeta.n_rows]
        return jnp.concatenate(outs, axis=0)[a["inv_perm"]]
    outs = []
    for bmeta, ab in zip(meta.buckets, a["buckets"]):
        if not bmeta.n_rows:
            continue
        # one fail point per sub-grid: a launch failure in ANY bucket
        # aborts the whole multi-grid call, so fallback handling
        # (exec.fallback.ResilientPlan) demotes the call consistently
        # instead of stitching a half-bucketed output
        chaos.fail_point("exec.pallas_launch")
        outs.append(_pallas_bucket(meta, bmeta, ab, x))
    y = jnp.concatenate(outs, axis=0)[a["inv_perm"]]
    fb = (x * a["s_in"][:, None] * a["s_out"][:, None] if meta.add_diag
          else jnp.zeros_like(x))
    return chaos.mangle("exec.kernel_result",
                        jnp.where(a["node_active"][:, None], y, fb))


# ---------------------------------------------------------------------------
# the plan container
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class GraphExecutionPlan:
    """Everything the hot path needs, compiled from a Graph once.

    The block-ELL structures are built eagerly for the ``pallas``/``jnp``
    backends (their side arrays come from the tiles) but **lazily** for
    ``coo`` — the coo compute path only needs the sorted edge arrays, so a
    Reddit-scale serve session should not pay two block-ELL constructions
    just to make ``describe()`` possible."""

    mode: str
    backend: str
    compact: bool
    bm: int
    bk: int
    num_nodes: int
    add_diag: bool
    meta_fwd: SideMeta
    meta_bwd: SideMeta
    _fwd: Dict[str, jax.Array]
    _bwd: Dict[str, jax.Array]
    _ell: Optional[BlockEll] = dataclasses.field(default=None, repr=False)
    _ell_t: Optional[BlockEll] = dataclasses.field(default=None, repr=False)
    _g_adj: Optional[Graph] = dataclasses.field(default=None, repr=False)
    _g_adj_t: Optional[Graph] = dataclasses.field(default=None, repr=False)
    _storage: str = "auto"
    _width: Optional[int] = None
    _fn: Optional[Callable] = dataclasses.field(default=None, repr=False)
    buckets: str = ""                 # bucket signature, "" = single grid
    _plan_bytes: int = 0              # device bytes both directions ship
    _occupancy: list = dataclasses.field(default_factory=list, repr=False)

    @property
    def ell(self) -> BlockEll:
        if self._ell is None:
            self._ell = build_blockell(self._g_adj, bm=self.bm, bk=self.bk,
                                       width=self._width,
                                       storage=self._storage)
        return self._ell

    @property
    def ell_t(self) -> BlockEll:
        if self._ell_t is None:
            self._ell_t = build_blockell(self._g_adj_t, bm=self.bm,
                                         bk=self.bk, storage=self._storage)
        return self._ell_t

    # ------------------------------------------------------------- execute
    def raw_apply(self, x: jax.Array) -> jax.Array:
        """One forward aggregation with NO custom VJP attached — the building
        block :class:`LayerExecutionPlan` composes inside its own VJP."""
        return _run_side(self.meta_fwd, self._fwd, x)

    def raw_apply_t(self, g: jax.Array) -> jax.Array:
        """One aggregation through the precompiled TRANSPOSE plan (``Aᵀ`` with
        the scales swapped) — the cotangent hot path for layer plans."""
        return _run_side(self.meta_bwd, self._bwd, g)

    def apply(self, x: jax.Array) -> jax.Array:
        """Differentiable fused aggregation; one launch on the hot path."""
        if self._fn is None:
            meta_f, meta_b = self.meta_fwd, self.meta_bwd
            af, ab = self._fwd, self._bwd

            @jax.custom_vjp
            def f(x):
                return _run_side(meta_f, af, x)

            def fwd(x):
                return f(x), None

            def bwd(_, g):
                return (_run_side(meta_b, ab, g),)

            f.defvjp(fwd, bwd)
            self._fn = f
        return self._fn(x)

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.apply(x)

    # ------------------------------------------------------------ geometry
    @property
    def n_active(self) -> int:
        if self.buckets:
            return sum(m.n_active for m in self.meta_fwd.buckets)
        return self.ell.n_active

    @property
    def grid_size(self) -> int:
        """Accumulation steps one forward launch performs: ``n_active`` for
        the compacted grid, ``R * W`` for the padded one, nnz for coo; for a
        bucketed plan, the sum over sub-grids (compacted on pallas, padded
        at per-bucket widths on jnp)."""
        if self.buckets:
            ms = self.meta_fwd.buckets
            if self.backend == "pallas":
                return sum(m.n_active for m in ms)
            return sum(m.R * m.W for m in ms if m.n_rows)
        if self.backend == "coo":
            return int(self._fwd["src"].shape[0])
        if self.compact:
            return self.ell.n_active
        return self.ell.n_row_blocks * self.ell.width

    def describe(self, d: int = 128) -> dict:
        if self.buckets:
            return {
                "mode": self.mode, "backend": self.backend,
                "compact": self.compact, "bm": self.bm, "bk": self.bk,
                "buckets": self.buckets,
                "bucket_occupancy": list(self._occupancy),
                "grid_size": self.grid_size,
                "plan_bytes": self._plan_bytes,
            }
        return {
            "mode": self.mode, "backend": self.backend,
            "compact": self.compact, "bm": self.bm, "bk": self.bk,
            "grid_size": self.grid_size,
            "padded_grid_size": self.ell.n_row_blocks * self.ell.width,
            "plan_bytes": self._plan_bytes,
            **self.traffic(d),
        }

    def traffic(self, d: int) -> dict:
        """:func:`traffic_model` of one forward launch at width ``d``, with
        the adjacency counted at the tile dtype this plan ships."""
        blocks = self._fwd.get("blocks")
        return traffic_model(self.ell, d, adj_itemsize=(
            None if blocks is None else blocks.dtype.itemsize))


# ---------------------------------------------------------------------------
# building
# ---------------------------------------------------------------------------
def _mode_scales(mode: str, g: Graph):
    deg = g.in_degrees().astype(np.float32)
    if mode == "gcn":
        s = 1.0 / np.sqrt(np.maximum(deg + 1.0, 1.0))
        return s, s, True
    if mode == "sum":
        ones = np.ones(g.num_nodes, np.float32)
        return ones, ones, False
    if mode == "mean":
        return (np.ones(g.num_nodes, np.float32),
                (1.0 / np.maximum(deg, 1.0)).astype(np.float32), False)
    raise ValueError(f"unknown plan mode {mode!r}; expected one of {MODES}")


def _pad_scale(s: np.ndarray, blocks: int, width: int) -> jnp.ndarray:
    """Per-node scale as the kernels read it: a zero-padded
    ``(blocks * width, 1)`` column, one ``(width, 1)`` block per tile."""
    out = np.zeros((blocks * width, 1), np.float32)
    out[:s.shape[0], 0] = s
    return jnp.asarray(out)


def _tile_dtype(ell: BlockEll, backend: str):
    """Adjacency tile dtype a backend ships: the Pallas kernels take
    unit-weight tiles as int8 (the TPU compiler casts int8, not uint8, to
    float32); everything else computes on float32 tiles."""
    return np.int8 if ell.implicit and backend == "pallas" else np.float32


def _shipped_bytes(a) -> int:
    """Bytes of the device arrays one direction of a plan holds."""
    if isinstance(a, dict):
        return sum(_shipped_bytes(v) for v in a.values())
    if isinstance(a, list):
        return sum(_shipped_bytes(v) for v in a)
    return int(a.nbytes)


def _side_arrays(ell: BlockEll, s_in: np.ndarray, s_out: np.ndarray,
                 backend: str, compact: bool) -> Dict[str, jax.Array]:
    R, C = ell.n_row_blocks, int(np.ceil(ell.num_nodes / ell.bk))
    a: Dict[str, jax.Array] = {"s_in": jnp.asarray(s_in),
                               "s_out": jnp.asarray(s_out)}
    if backend == "pallas":
        a["s_in2d"] = _pad_scale(s_in, C, ell.bk)
        a["s_out2d"] = _pad_scale(s_out, R, ell.bm)
    if compact:
        comp = ell.compact(_tile_dtype(ell, backend))
        a["rows"] = jnp.asarray(comp.rows)
        a["cols"] = jnp.asarray(comp.cols)
        a["blocks"] = jnp.asarray(comp.blocks)
        node_active = np.repeat(comp.row_active, ell.bm)[:ell.num_nodes]
        a["node_active"] = jnp.asarray(node_active)
    else:
        a["block_cols"] = jnp.asarray(ell.block_cols)
        a["blocks"] = jnp.asarray(ell.dense_blocks(_tile_dtype(ell, backend)))
    return a


def _bucketed_side_arrays(g: Graph, scheme, s_in: np.ndarray,
                          s_out: np.ndarray, backend: str, storage: str):
    """Per-bucket arrays + metas for ONE direction of a bucketed plan.

    Destination nodes are partitioned by ``g``'s in-degrees (so the
    transpose direction re-buckets by its own skew) and remapped to a
    bucket-local contiguous row space; sources stay global.  Returns
    ``(arrays, metas)``.
    """
    n = g.num_nodes
    valid = (g.edge_mask if g.edge_mask is not None
             else np.ones(g.num_edges, bool))
    src = g.src[valid].astype(np.int64)
    dst = g.dst[valid].astype(np.int64)
    w = (g.edge_weight[valid] if g.edge_weight is not None
         else np.ones(src.shape[0], np.float32))
    idx_list = assign_buckets(g.in_degrees(), scheme)
    bucket_of = np.zeros(n, np.int64)
    local_of = np.zeros(n, np.int64)
    for b, idx in enumerate(idx_list):
        bucket_of[idx] = b
        local_of[idx] = np.arange(idx.size)
    dst_bucket = bucket_of[dst]

    metas, buckets_a = [], []
    node_active = np.zeros(n, bool)
    for b, ((bm_b, _cut), idx) in enumerate(zip(scheme, idx_list)):
        if idx.size == 0:
            metas.append(BucketMeta(bm=bm_b, bk=bm_b, R=0, C=0, W=0,
                                    n_active=0, n_rows=0))
            buckets_a.append({})
            continue
        sel = dst_bucket == b
        ell_b = build_blockell_coo(
            src[sel], local_of[dst[sel]], w[sel], num_nodes=n,
            num_rows=int(idx.size), bm=bm_b, bk=bm_b, storage=storage)
        ab: Dict[str, jax.Array] = {"idx": jnp.asarray(idx.astype(np.int32))}
        if backend == "jnp":
            ab["block_cols"] = jnp.asarray(ell_b.block_cols)
            ab["blocks"] = jnp.asarray(ell_b.dense_blocks(np.float32))
            ab["s_out_sel"] = jnp.asarray(s_out[idx].astype(np.float32))
            node_active[idx] = True          # jnp computes every bucket row
            n_act = ell_b.n_active
        else:
            comp = ell_b.compact(_tile_dtype(ell_b, backend))
            ab["rows"] = jnp.asarray(comp.rows)
            ab["cols"] = jnp.asarray(comp.cols)
            ab["blocks"] = jnp.asarray(comp.blocks)
            ab["s_in2d"] = _pad_scale(s_in, int(np.ceil(n / bm_b)), bm_b)
            ab["s_out2d"] = _pad_scale(s_out[idx], ell_b.n_row_blocks, bm_b)
            ab["s_in_diag2d"] = _pad_scale(s_in[idx], ell_b.n_row_blocks,
                                           bm_b)
            node_active[idx] = np.repeat(comp.row_active, bm_b)[:idx.size]
            n_act = comp.n_active
        metas.append(BucketMeta(bm=bm_b, bk=bm_b, R=ell_b.n_row_blocks,
                                C=int(np.ceil(n / bm_b)), W=ell_b.width,
                                n_active=int(n_act), n_rows=int(idx.size)))
        buckets_a.append(ab)

    perm = np.concatenate([idx for idx in idx_list if idx.size])
    inv = np.zeros(n, np.int64)
    inv[perm] = np.arange(n)
    a: Dict[str, jax.Array] = {
        "s_in": jnp.asarray(s_in), "s_out": jnp.asarray(s_out),
        "buckets": buckets_a,
        "inv_perm": jnp.asarray(inv.astype(np.int32)),
    }
    if backend == "pallas":
        a["node_active"] = jnp.asarray(node_active)
    return a, tuple(metas)


def _coo_arrays(g: Graph, s_in: np.ndarray, s_out: np.ndarray,
                add_diag: bool, weighted: bool) -> Dict[str, jax.Array]:
    valid = (g.edge_mask if g.edge_mask is not None
             else np.ones(g.num_edges, bool))
    src = g.src[valid].astype(np.int32)
    dst = g.dst[valid].astype(np.int32)
    w = s_out[dst] * s_in[src]
    if weighted and g.edge_weight is not None:
        w = w * g.edge_weight[valid]
    order = np.argsort(dst, kind="stable")   # dst-major: scatter locality
    out = {"src": jnp.asarray(src[order]), "dst": jnp.asarray(dst[order]),
           "w": jnp.asarray(w[order].astype(np.float32))}
    if add_diag:
        out["dvec"] = jnp.asarray((s_out * s_in).astype(np.float32))
    return out


def build_plan(g: Graph, mode: str = "gcn", *,
               bm: Optional[int] = None, bk: Optional[int] = None,
               backend: Optional[str] = None, compact: bool = True,
               storage: str = "auto", weighted: bool = False,
               interpret: Optional[bool] = None,
               width: Optional[int] = None,
               buckets: str = "") -> GraphExecutionPlan:
    """Compile ``g`` into a :class:`GraphExecutionPlan`.

    ``backend=None`` picks ``"pallas"`` on TPU and ``"coo"`` elsewhere (use
    :func:`repro.exec.autotune_plan` to pick by measurement instead).  Square
    blocks are required (the transpose plan reuses the same tiling).

    ``buckets`` is a degree-bucket signature (``"64@8+256"``: tile 64 for
    in-degree < 8, tile 256 for the rest — see :mod:`repro.exec.bucketing`):
    the plan then launches one sub-grid per bucket with that bucket's own
    square tile and stitches the outputs, on the ``pallas`` (compact
    sub-grids) and ``jnp`` (per-bucket padded einsum) backends.
    """
    scheme = parse_bucket_sig(buckets)
    if scheme:
        bm = bk = max(b for b, _ in scheme)
    bm = bm or 128
    bk = bk or bm
    if bm != bk:
        raise ValueError("GraphExecutionPlan requires square blocks "
                         f"(got bm={bm}, bk={bk})")
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "coo"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    if scheme and backend == "coo":
        raise ValueError("degree buckets need a block backend "
                         "(pallas or jnp), not coo")
    if scheme and not compact:
        raise ValueError("bucketed plans imply slot compaction "
                         "(compact=True)")
    if weighted and mode != "sum":
        raise ValueError("weighted adjacency only composes with mode='sum'")
    interp = ((jax.default_backend() != "tpu") if interpret is None
              else interpret)
    s_in, s_out, add_diag = _mode_scales(mode, g)

    g_adj = g if weighted else dataclasses.replace(g, edge_weight=None)
    g_adj_t = transpose_graph(g_adj)

    def meta_for(n_active: int) -> SideMeta:
        R = int(np.ceil(g.num_nodes / bm))
        return SideMeta(backend=backend, compact=compact, add_diag=add_diag,
                        bm=bm, bk=bk, R=R, C=int(np.ceil(g.num_nodes / bk)),
                        n_active=n_active, n=g.num_nodes, interpret=interp)

    occupancy: list = []
    with obs.span("exec.plan.compile", cat="exec", backend=backend,
                  mode=mode, bm=bm, compact=compact, n=g.num_nodes,
                  buckets=buckets) as sp:
        if scheme:
            # each direction bucketed by ITS OWN in-degrees: per-bucket
            # transpose plans for the VJP
            fwd, metas_f = _bucketed_side_arrays(
                g_adj, scheme, s_in, s_out, backend, storage)
            bwd, metas_b = _bucketed_side_arrays(
                g_adj_t, scheme, s_out, s_in, backend, storage)
            ell = ell_t = None
            meta_f = BucketedSideMeta(backend=backend, compact=compact,
                                      add_diag=add_diag, n=g.num_nodes,
                                      interpret=interp, buckets=metas_f)
            meta_b = BucketedSideMeta(backend=backend, compact=compact,
                                      add_diag=add_diag, n=g.num_nodes,
                                      interpret=interp, buckets=metas_b)
            occupancy = bucket_occupancy(g.in_degrees(), scheme)
            for i, occ in enumerate(occupancy):
                obs.gauge("exec.plan.bucket_nodes", bucket=i,
                          bm=occ["bm"]).set(occ["nodes"])
                obs.gauge("exec.plan.bucket_edges", bucket=i,
                          bm=occ["bm"]).set(occ["edges"])
            sp.set(n_active=sum(m.n_active for m in metas_f))
        elif backend == "coo":
            # the coo path never touches tiles: defer block-ELL to first
            # access
            fwd = _coo_arrays(g_adj, s_in, s_out, add_diag, weighted)
            bwd = _coo_arrays(g_adj_t, s_out, s_in, add_diag, weighted)
            ell = ell_t = None
            meta_f, meta_b = meta_for(0), meta_for(0)
        else:
            ell = build_blockell(g_adj, bm=bm, bk=bk, width=width,
                                 storage=storage)
            ell_t = build_blockell(g_adj_t, bm=bm, bk=bk, storage=storage)
            fwd = _side_arrays(ell, s_in, s_out, backend, compact)
            bwd = _side_arrays(ell_t, s_out, s_in, backend, compact)
            meta_f, meta_b = meta_for(ell.n_active), meta_for(ell_t.n_active)
            sp.set(n_active=ell.n_active)
        plan_bytes = _shipped_bytes(fwd) + _shipped_bytes(bwd)
        sp.set(plan_bytes=plan_bytes)
    obs.counter("exec.plan.compiles", backend=backend).inc()
    return GraphExecutionPlan(
        mode=mode, backend=backend, compact=compact, bm=bm, bk=bk,
        num_nodes=g.num_nodes, add_diag=add_diag,
        meta_fwd=meta_f, meta_bwd=meta_b, _fwd=fwd, _bwd=bwd,
        _ell=ell, _ell_t=ell_t, _g_adj=g_adj, _g_adj_t=g_adj_t,
        _storage=storage, _width=width, buckets=buckets,
        _plan_bytes=plan_bytes, _occupancy=occupancy)


# ===========================================================================
# Hierarchical layer fusion (ISSUE 4): fold the node-level update matmul
# into the graph-level aggregation, with computation-order selection.
# ===========================================================================
def layer_order_costs(n: int, e: int, d_in: int, d_out: int, *,
                      bytes_per_el: int = 4, balance: float = 8.0) -> dict:
    """FLOP/byte model of the two computation orders of one GNN layer.

    A layer is ``act(F(x) @ W [+ b])`` with ``F`` the (linear) graph-level
    aggregation; linearity means ``F(x) W == F(x W)``, so the scheduler may
    run the SpMM at width ``d_in`` (aggregate-first) or ``d_out``
    (update-first).  The update matmul costs the same either way — the
    decision is purely which feature width the aggregation streams:

        aggregate_first: spmm(d_in)  + matmul(n, d_in, d_out)
        update_first:    matmul(n, d_in, d_out) + spmm(d_out)

    Costs are byte-equivalents ``bytes + flops / balance`` (``balance`` =
    flops-per-byte at the roofline ridge), so the verdict is the same on any
    hardware whose ridge sits within a wide band; :mod:`repro.exec.autotune`
    validates it by measurement anyway.
    """
    def spmm(d: int) -> float:
        return spmm_cost(n, e, d, bytes_per_el=bytes_per_el, balance=balance)

    matmul = ((n * d_in + n * d_out + d_in * d_out) * bytes_per_el
              + 2.0 * n * d_in * d_out / balance)
    return {"aggregate_first": spmm(d_in) + matmul,
            "update_first": matmul + spmm(d_out)}


def spmm_cost(n: int, e: int, d: int, *, bytes_per_el: int = 4,
              balance: float = 8.0) -> float:
    """Byte-equivalent cost of one SpMM at feature width ``d`` — the unit
    the whole cold cost model (and its calibration, :mod:`repro.obs.audit`)
    is denominated in."""
    flops = 2.0 * e * d
    bytes_ = (e * d + 2.0 * n * d) * bytes_per_el   # gathers + in/out rows
    return bytes_ + flops / balance


def choose_order(n: int, e: int, d_in: int, d_out: int) -> str:
    """Pick the computation order from the FLOP/byte model: shrinking layers
    (``d_out < d_in``) aggregate fewer bytes after the update, growing layers
    before it.  Ties go to aggregate-first, which is the fusable order."""
    c = layer_order_costs(n, e, d_in, d_out)
    return ("update_first" if c["update_first"] < c["aggregate_first"]
            else "aggregate_first")


def _pad128(d: int) -> int:
    return -(-d // 128) * 128


def _self_term(x: jax.Array, w_self: jax.Array, self_coeff) -> jax.Array:
    """The epilogue's self half ``self_coeff * (x @ w_self)`` (coeff may be a
    traced scalar of shape () or (1,), or None for 1)."""
    s = x @ w_self
    if self_coeff is not None:
        s = s * jnp.reshape(self_coeff, ())
    return s


def _bucketed_layer(meta: BucketedSideMeta, a: Dict[str, jax.Array],
                    x: jax.Array, w: jax.Array, b: Optional[jax.Array],
                    relu: bool, w_self: Optional[jax.Array] = None,
                    self_coeff=None) -> jax.Array:
    """Fused layer over degree buckets: one update-epilogue compact launch
    per bucket (destination-row operands gathered into bucket-local order),
    outputs stitched through the inverse permutation."""
    n, d_in = x.shape
    d_out = w.shape[1]
    dp_in, dp_out = _pad128(d_in), _pad128(d_out)
    wp = jnp.pad(w, ((0, dp_in - d_in), (0, dp_out - d_out)))
    bp = (None if b is None
          else jnp.pad(b, (0, dp_out - d_out)).reshape(1, dp_out))
    wsp = (None if w_self is None
           else jnp.pad(w_self, ((0, dp_in - d_in), (0, dp_out - d_out))))
    cf = (None if self_coeff is None
          else jnp.reshape(jnp.asarray(self_coeff, jnp.float32), (1, 1)))
    outs = []
    for bmeta, ab in zip(meta.buckets, a["buckets"]):
        if bmeta.n_rows == 0:
            continue
        if bmeta.n_active == 0:
            outs.append(jnp.zeros((bmeta.n_rows, d_out), x.dtype))
            continue
        # per-sub-grid fail point: any bucket's launch failure aborts the
        # whole fused-layer call (consistent demotion, no half-stitched y)
        chaos.fail_point("exec.pallas_launch")
        bm, bk, R, C = bmeta.bm, bmeta.bk, bmeta.R, bmeta.C
        xp = jnp.pad(x, ((0, C * bk - n), (0, dp_in - d_in)))
        xg = None
        if meta.add_diag or w_self is not None:
            xg = jnp.pad(x[ab["idx"]],
                         ((0, R * bm - bmeta.n_rows), (0, dp_in - d_in)))
        y = spmm_blockell_update_compact(
            ab["rows"], ab["cols"], ab["blocks"], xp, ab["s_in2d"],
            ab["s_out2d"], wp, bp, wsp, cf,
            x_self=xg if w_self is not None else None,
            x_diag=xg if meta.add_diag else None,
            s_in_diag=ab["s_in_diag2d"] if meta.add_diag else None,
            bm=bm, bk=bk, n_row_blocks=R, add_diag=meta.add_diag,
            relu=relu, interpret=meta.interpret)
        outs.append(y[:bmeta.n_rows, :d_out])
    y = jnp.concatenate(outs, axis=0)[a["inv_perm"]]
    fb = (x * (a["s_in"] * a["s_out"])[:, None] @ w if meta.add_diag
          else jnp.zeros((n, d_out), x.dtype))
    if w_self is not None:
        fb = fb + _self_term(x, w_self, self_coeff)
    if b is not None:
        fb = fb + b
    if relu:
        fb = jnp.maximum(fb, 0.0)
    return chaos.mangle("exec.kernel_result",
                        jnp.where(a["node_active"][:, None], y, fb))


def _pallas_layer(meta, a: Dict[str, jax.Array], x: jax.Array,
                  w: jax.Array, b: Optional[jax.Array], relu: bool,
                  w_self: Optional[jax.Array] = None, self_coeff=None
                  ) -> jax.Array:
    """One fused layer launch: SpMM + (two-)W-update epilogue (+bias/ReLU)."""
    if isinstance(meta, BucketedSideMeta):
        return _bucketed_layer(meta, a, x, w, b, relu, w_self, self_coeff)
    chaos.fail_point("exec.pallas_launch")   # no-op unless a drill armed it
    n, d_in = x.shape
    d_out = w.shape[1]
    bm, bk, R, C = meta.bm, meta.bk, meta.R, meta.C
    dp_in, dp_out = _pad128(d_in), _pad128(d_out)
    xp = jnp.pad(x, ((0, C * bk - n), (0, dp_in - d_in)))
    wp = jnp.pad(w, ((0, dp_in - d_in), (0, dp_out - d_out)))
    bp = (None if b is None
          else jnp.pad(b, (0, dp_out - d_out)).reshape(1, dp_out))
    wsp = (None if w_self is None
           else jnp.pad(w_self, ((0, dp_in - d_in), (0, dp_out - d_out))))
    cf = (None if self_coeff is None
          else jnp.reshape(jnp.asarray(self_coeff, jnp.float32), (1, 1)))
    if meta.compact:
        y = None
        if meta.n_active:
            y = spmm_blockell_update_compact(
                a["rows"], a["cols"], a["blocks"], xp, a["s_in2d"],
                a["s_out2d"], wp, bp, wsp, cf, bm=bm, bk=bk, n_row_blocks=R,
                add_diag=meta.add_diag, relu=relu, interpret=meta.interpret)
        # rows whose destination block has no active slot: the analytic
        # diagonal and self terms go through the same update epilogue outside
        fb = (x * (a["s_in"] * a["s_out"])[:, None] @ w if meta.add_diag
              else jnp.zeros((n, d_out), x.dtype))
        if w_self is not None:
            fb = fb + _self_term(x, w_self, self_coeff)
        if b is not None:
            fb = fb + b
        if relu:
            fb = jnp.maximum(fb, 0.0)
        if y is None:
            return chaos.mangle("exec.kernel_result", fb)
        return chaos.mangle("exec.kernel_result",
                            jnp.where(a["node_active"][:, None],
                                      y[:n, :d_out], fb))
    y = spmm_blockell_update(
        a["block_cols"], a["blocks"], xp, a["s_in2d"], a["s_out2d"], wp, bp,
        wsp, cf, bm=bm, bk=bk, add_diag=meta.add_diag, relu=relu,
        interpret=meta.interpret)
    return chaos.mangle("exec.kernel_result", y[:n, :d_out])


@dataclasses.dataclass
class LayerExecutionPlan:
    """A whole GNN layer, compiled: aggregation ∘ update as one scheduled op.

    ``apply(x, w, b, relu=...)`` computes ``act(F(x) @ w + b)`` where ``F``
    is the owned :class:`GraphExecutionPlan`'s aggregation.  Because ``F`` is
    linear the plan may evaluate it as ``act(F(x @ w) + b)`` instead
    (``order="update_first"``) — chosen by :func:`choose_order` and validated
    by :func:`repro.exec.autotune_layer` — and, on the Pallas backend in
    aggregate-first order, runs SpMM + update + bias + ReLU as ONE launch
    (``fuse=True``; kernels/spmm_blockell.py ``spmm_blockell_update*``).

    The generalized TWO-W epilogue (ISSUE 5) adds an optional self half:

        y = act( F(x) @ w  +  self_coeff * (x @ w_self)  +  b )

    with ``self_coeff`` an optional TRACED scalar (default 1).  GraphSAGE's
    concat form ``concat(h, F(h)) @ W == h @ W[:d] + F(h) @ W[d:]`` and GIN's
    ``((1+ε) h + F(h)) @ W`` (pass ``w_self=w`` and ``self_coeff=1+ε``) each
    become one plan call — one kernel launch per layer when fused.

    The custom VJP runs ONE aggregation through the precompiled transpose
    plan and mirrors the forward's computation order (``y = M x W + b``
    either way, so both forms are exact):

    * update-first / fused: ``h = Mᵀ ḡ`` (width ``d_out``), then
      ``dx = h Wᵀ`` and ``dW = Σ_v x_v ⊗ h_v`` (a node-axis reduction);
    * aggregate-first unfused: the forward's aggregation ``agg = M x`` is
      the residual, then ``u = ḡ Wᵀ``, ``dx = Mᵀ u`` (width ``d_in``) and
      ``dW = aggᵀ ḡ`` — the transpose SpMM always streams the NARROW side,
      exactly like the forward.  ``db = Σ ḡ``; the backward never re-runs
      the forward.  The self half never touches the aggregation:
      ``dx += c ḡ W_selfᵀ``, ``dW_self = c xᵀ ḡ`` and
      ``dc = ⟨W_self, xᵀ ḡ⟩`` share one ``xᵀ ḡ`` product.
    """

    gplan: GraphExecutionPlan
    d_in: int
    d_out: int
    order: str
    fuse: bool
    model_order: str = ""
    _fns: Dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def mode(self) -> str:
        return self.gplan.mode

    @property
    def backend(self) -> str:
        return self.gplan.backend

    @property
    def num_nodes(self) -> int:
        return self.gplan.num_nodes

    def _layer_fn(self, has_bias: bool, relu: bool, has_self: bool = False,
                  has_coeff: bool = False) -> Callable:
        key = (has_bias, relu, has_self, has_coeff)
        if key in self._fns:
            return self._fns[key]
        gp, order, fuse = self.gplan, self.order, self.fuse
        meta_f, af = gp.meta_fwd, gp._fwd
        meta_b, ab = gp.meta_bwd, gp._bwd

        # the backward mirrors the forward's order so the transpose SpMM
        # always streams the narrow feature side (see class docstring);
        # fused layers keep no aggregation residual, so they use the
        # d_out-side form
        agg_residual = order == "aggregate_first" and not fuse

        def post(y, b):
            if b is not None:
                y = y + b
            return jnp.maximum(y, 0.0) if relu else y

        def forward(x, w, b, ws, c):
            if fuse:
                return _pallas_layer(meta_f, af, x, w, b, relu, ws, c)
            y = (_run_side(meta_f, af, x) @ w if order == "aggregate_first"
                 else _run_side(meta_f, af, x @ w))
            if ws is not None:
                y = y + _self_term(x, ws, c)
            return post(y, b)

        def fwd_core(x, w, b, ws, c):
            if agg_residual:
                agg = _run_side(meta_f, af, x)
                y = agg @ w
                if ws is not None:
                    y = y + _self_term(x, ws, c)
                y = post(y, b)
                # the self half's dW_self/dc need x; without it the agg
                # residual alone suffices
                return y, (agg, x if ws is not None else None, w, ws, c, y)
            y = forward(x, w, b, ws, c)
            return y, (None, x, w, ws, c, y)

        def bwd_core(res, g):
            agg, x, w, ws, c, y = res
            if relu:
                g = jnp.where(y > 0, g, 0.0)
            if agg is not None:
                # agg = M x: dx = Mᵀ (ḡ Wᵀ) runs at width d_in and
                # dW = aggᵀ ḡ reuses the forward's aggregation
                dx = _run_side(meta_b, ab, g @ w.T)
                dw = jnp.einsum("nd,ne->de", agg, g)
            else:
                # h = Mᵀ ḡ runs at width d_out, dW = Σ_v x_v ⊗ h_v
                h = _run_side(meta_b, ab, g)
                dx = h @ w.T
                dw = jnp.einsum("nd,ne->de", x, h)
            dws = dc = None
            if ws is not None:
                xtg = jnp.einsum("nd,ne->de", x, g)
                if c is not None:
                    cs = jnp.reshape(c, ())
                    dx = dx + cs * (g @ ws.T)
                    dws = cs * xtg
                    dc = jnp.reshape(jnp.vdot(ws, xtg), jnp.shape(c))
                else:
                    dx = dx + g @ ws.T
                    dws = xtg
            return g, dx, dw, dws, dc

        # one fixed-arity custom_vjp covers every optional-operand combo:
        # absent operands ride through as None (empty pytrees) and get None
        # cotangents back
        @jax.custom_vjp
        def f(x, w, b, ws, c):
            return forward(x, w, b, ws, c)

        def fwd(x, w, b, ws, c):
            return fwd_core(x, w, b, ws, c)

        def bwd(res, g):
            g, dx, dw, dws, dc = bwd_core(res, g)
            db = jnp.sum(g, axis=0) if has_bias else None
            return dx, dw, db, dws, dc

        f.defvjp(fwd, bwd)
        self._fns[key] = f
        return f

    def apply(self, x: jax.Array, w: jax.Array,
              b: Optional[jax.Array] = None, *, relu: bool = False,
              w_self: Optional[jax.Array] = None, self_coeff=None
              ) -> jax.Array:
        """Differentiable fused layer
        ``act(F(x) @ w + self_coeff * (x @ w_self) + b)``."""
        if x.shape[0] != self.num_nodes:
            raise ValueError(f"plan compiled for {self.num_nodes} nodes but "
                             f"x has {x.shape[0]} rows (wrong graph?)")
        if w.shape != (self.d_in, self.d_out):
            raise ValueError(f"layer plan compiled for W {self.d_in}x"
                             f"{self.d_out}, got {w.shape}")
        if w_self is not None and tuple(w_self.shape) != (self.d_in,
                                                          self.d_out):
            raise ValueError(f"w_self must match W {self.d_in}x{self.d_out}, "
                             f"got {w_self.shape}")
        if self_coeff is not None and w_self is None:
            raise ValueError("self_coeff needs w_self (the self half it "
                             "scales)")
        fn = self._layer_fn(b is not None, relu, w_self is not None,
                            self_coeff is not None)
        return fn(x, w, b, w_self, self_coeff)

    def __call__(self, x, w, b=None, *, relu: bool = False, w_self=None,
                 self_coeff=None) -> jax.Array:
        return self.apply(x, w, b, relu=relu, w_self=w_self,
                          self_coeff=self_coeff)

    def describe(self) -> dict:
        return {"order": self.order, "fuse": self.fuse,
                "model_order": self.model_order,
                "d_in": self.d_in, "d_out": self.d_out,
                **self.gplan.describe(self.d_in if
                                      self.order == "aggregate_first"
                                      else self.d_out)}


def build_layer_plan(g: Graph, mode: str = "gcn", *, d_in: int, d_out: int,
                     order: str = "auto", fuse: Optional[bool] = None,
                     bm: Optional[int] = None, bk: Optional[int] = None,
                     backend: Optional[str] = None, compact: bool = True,
                     storage: str = "auto", interpret: Optional[bool] = None,
                     gplan: Optional[GraphExecutionPlan] = None,
                     buckets: str = "") -> LayerExecutionPlan:
    """Compile one GNN layer of shape ``(d_in -> d_out)`` over ``g``.

    ``order="auto"`` consults the FLOP/byte model; ``fuse=None`` turns the
    one-launch Pallas layer kernel on exactly when it is applicable (pallas
    backend, aggregate-first order).  Pass a prebuilt ``gplan`` to share one
    block-ELL construction across the layers of a model.
    """
    model_order = choose_order(g.num_nodes, g.num_valid_edges, d_in, d_out)
    if order in (None, "auto"):
        order = model_order
    if order not in ORDERS:
        raise ValueError(f"unknown order {order!r}; expected {ORDERS}")
    if gplan is None:
        gplan = build_plan(g, mode, bm=bm, bk=bk, backend=backend,
                           compact=compact, storage=storage,
                           interpret=interpret, buckets=buckets)
    elif gplan.mode != mode:
        raise ValueError(f"prebuilt gplan has mode {gplan.mode!r}, layer "
                         f"plan wants {mode!r}")
    fusable = gplan.backend == "pallas" and order == "aggregate_first"
    if fuse is None:
        fuse = fusable
    elif fuse and not fusable:
        raise ValueError("fuse=True requires backend='pallas' and "
                         f"order='aggregate_first' (got {gplan.backend!r}, "
                         f"{order!r})")
    return LayerExecutionPlan(gplan=gplan, d_in=d_in, d_out=d_out,
                              order=order, fuse=fuse,
                              model_order=model_order)
