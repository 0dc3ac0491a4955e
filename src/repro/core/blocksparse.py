"""Block-sparse (block-ELL) adjacency construction — the TPU G-D cache.

After LSH reordering, community edges concentrate near the diagonal of the
adjacency matrix, so tiling it into (bm x bk) blocks yields few *active*
blocks with high internal density.  The Pallas SpMM kernel then streams one
(bk x d) source-feature tile into VMEM per active block and reuses it for all
bm destinations — exactly the temporal reuse the paper's per-PE G-D cache
provides, with block density playing the role of cache hit rate.

Format: block-ELL.  For each of ``n_row_blocks`` destination blocks we keep a
fixed-width list of source-block ids (padded with -1) plus the weight tile
for each slot.  Two storage regimes:

* ``dense``   — (R, W, bm, bk) tiles in the graph's native weight dtype;
* ``bitmask`` — implicit-weight fast path for unweighted adjacencies
  (normalized-GCN aggregation runs unweighted on pre-scaled features): only
  a packed 0/1 mask (R, W, bm, ceil(bk/8)) uint8 is stored, 32x smaller
  than fp32 tiles.  ``dense_blocks()`` materializes compute tiles on demand.

``compact()`` flattens the padded (R, W) slot table into row-major-sorted
active-slot lists — the form the slot-compacted Pallas kernel iterates so
its grid has exactly ``n_active`` steps instead of ``R * W``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from ..graph.structure import Graph


@dataclasses.dataclass(frozen=True)
class BlockCompaction:
    """Row-major-sorted active slots of a BlockEll (the compacted grid).

    rows / cols: (n_active,) int32 block coordinates, sorted by (row, col);
    blocks:      (n_active, bm, bk) weight tiles in the compute dtype;
    row_active:  (R,) bool — destination blocks with at least one active slot
                 (rows the compacted kernel visits; the rest need a fallback);
    row_offsets: (R + 1,) int64 CSR-style offsets into rows/cols per row block.
    """

    rows: np.ndarray
    cols: np.ndarray
    blocks: np.ndarray
    row_active: np.ndarray
    row_offsets: np.ndarray

    @property
    def n_active(self) -> int:
        return int(self.rows.shape[0])


@dataclasses.dataclass(frozen=True)
class BlockEll:
    """Block-ELL sparse matrix A (dst-major: rows = destinations).

    block_cols: (R, W) int32 source-block index per slot, -1 = inactive.
    blocks:     (R, W, bm, bk) dense weight tiles (None when ``packed`` set).
    packed:     (R, W, bm, ceil(bk/8)) uint8 packed 0/1 mask (implicit unit
                weights; None for dense storage).
    """

    block_cols: np.ndarray
    blocks: Optional[np.ndarray]
    num_nodes: int
    bm: int
    bk: int
    packed: Optional[np.ndarray] = None

    @property
    def n_row_blocks(self) -> int:
        return int(self.block_cols.shape[0])

    @property
    def width(self) -> int:
        return int(self.block_cols.shape[1])

    @property
    def n_active(self) -> int:
        return int((self.block_cols >= 0).sum())

    @property
    def implicit(self) -> bool:
        """True when only the packed bitmask (unit weights) is stored."""
        return self.blocks is None

    @property
    def dtype(self) -> np.dtype:
        return (np.dtype(np.float32) if self.blocks is None
                else self.blocks.dtype)

    # ------------------------------------------------------------- storage
    def dense_blocks(self, dtype=np.float32) -> np.ndarray:
        """(R, W, bm, bk) compute tiles, unpacking the bitmask if implicit."""
        if self.blocks is not None:
            return (self.blocks if self.blocks.dtype == dtype
                    else self.blocks.astype(dtype))
        R, W = self.block_cols.shape
        bits = np.unpackbits(self.packed, axis=-1, count=self.bk)
        return bits.reshape(R, W, self.bm, self.bk).astype(dtype)

    def storage_bytes(self) -> int:
        """Bytes the adjacency tiles occupy (the plan-memory satellite)."""
        tiles = self.packed if self.blocks is None else self.blocks
        return int(tiles.nbytes + self.block_cols.nbytes)

    def compact(self, dtype=np.float32) -> BlockCompaction:
        """Row-major-sorted active-slot view for the compacted kernel.

        Only the ``n_active`` live tiles are ever materialized — the padded
        (R, W, bm, bk) dense array is never built, so compacting an implicit
        (bitmask) plan keeps its ~32x memory advantage."""
        R, W = self.block_cols.shape
        r_idx, s_idx = np.nonzero(self.block_cols >= 0)
        cols = self.block_cols[r_idx, s_idx]
        order = np.lexsort((cols, r_idx))       # sort by (row, col)
        r_idx, s_idx, cols = r_idx[order], s_idx[order], cols[order]
        if self.blocks is not None:
            tiles = self.blocks[r_idx, s_idx].astype(dtype, copy=False)
        else:
            tiles = np.unpackbits(self.packed[r_idx, s_idx], axis=-1,
                                  count=self.bk).astype(dtype)
        row_active = np.zeros(R, bool)
        row_active[r_idx] = True
        row_offsets = np.zeros(R + 1, np.int64)
        np.add.at(row_offsets, r_idx + 1, 1)
        return BlockCompaction(rows=r_idx.astype(np.int32),
                               cols=cols.astype(np.int32),
                               blocks=tiles,
                               row_active=row_active,
                               row_offsets=np.cumsum(row_offsets))

    # --------------------------------------------------------------- stats
    def _nnz(self) -> int:
        if self.blocks is not None:
            return int((self.blocks != 0).sum())
        active = self.block_cols >= 0
        # popcount via unpackbits on active slots only
        return int(np.unpackbits(self.packed[active], axis=-1,
                                 count=self.bk).sum())

    def density_stats(self) -> dict:
        """Reuse metrics: active-block density == simulated G-D hit quality."""
        active = self.block_cols >= 0
        nnz = self._nnz()
        n_blocks_total = self.n_row_blocks * max(
            1, int(np.ceil(self.num_nodes / self.bk)))
        if self.blocks is not None:
            per_block_nnz = (self.blocks != 0).sum(axis=(2, 3))[active]
        else:
            per_block_nnz = np.unpackbits(
                self.packed[active], axis=-1, count=self.bk).sum(axis=(1, 2))
        return {
            "active_blocks": self.n_active,
            "total_blocks": n_blocks_total,
            "block_fill_fraction": self.n_active / max(n_blocks_total, 1),
            "mean_block_density": float(per_block_nnz.mean() / (self.bm * self.bk))
            if per_block_nnz.size else 0.0,
            "nnz": int(nnz),
            # bytes each chip must stream from HBM for one SpMM at feat dim d:
            # active_blocks * bk * d * 4  (vs nnz * d * 4 for pure gather)
            "feature_tile_loads": self.n_active,
            "storage_bytes": self.storage_bytes(),
            "implicit_weights": self.implicit,
        }


def build_blockell(g: Graph, bm: int = 128, bk: int = 128,
                   width: Optional[int] = None,
                   storage: str = "dense",
                   dtype: Optional[np.dtype] = None) -> BlockEll:
    """Tile the (reordered) adjacency into block-ELL.

    ``width`` fixes the slot count (static shape); defaults to the max active
    source blocks over destination blocks.  ``storage`` selects tile storage:
    ``"dense"`` keeps (R, W, bm, bk) tiles in ``dtype`` (default: the graph's
    edge-weight dtype, else float32); ``"bitmask"`` stores only a packed 0/1
    mask (requires unit weights and no duplicate edges); ``"auto"`` picks the
    bitmask whenever it is exact.
    """
    valid = g.edge_mask if g.edge_mask is not None else np.ones(g.num_edges, bool)
    src = g.src[valid].astype(np.int64)
    dst = g.dst[valid].astype(np.int64)
    w = (g.edge_weight[valid] if g.edge_weight is not None
         else np.ones(src.shape[0], np.float32))
    if dtype is None:
        dtype = w.dtype if g.edge_weight is not None else np.float32
    return build_blockell_coo(src, dst, w, num_nodes=g.num_nodes, bm=bm,
                              bk=bk, width=width, storage=storage,
                              dtype=dtype)


def build_blockell_coo(src: np.ndarray, dst: np.ndarray, w: np.ndarray, *,
                       num_nodes: int, num_rows: Optional[int] = None,
                       bm: int = 128, bk: int = 128,
                       width: Optional[int] = None, storage: str = "dense",
                       dtype: Optional[np.dtype] = None) -> BlockEll:
    """:func:`build_blockell` over bare COO arrays, possibly RECTANGULAR.

    ``num_rows`` decouples the destination-row count from the source-node
    count: the degree-bucketed plans (repro.exec.bucketing) remap each
    bucket's destination rows into a compact 0..n_b-1 space while sources
    stay global, so each bucket's block-ELL is an (n_b x num_nodes) matrix
    tiled at that bucket's own (bm, bk).  ``num_rows=None`` keeps the square
    single-grid behavior.
    """
    if storage not in ("dense", "bitmask", "auto"):
        raise ValueError(f"unknown storage {storage!r}")
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w)
    if dtype is None:
        dtype = np.float32
    n = num_nodes
    n_rows = num_rows if num_rows is not None else n
    R = max(int(np.ceil(n_rows / bm)), 1)
    C = int(np.ceil(n / bk))
    rb, cb = dst // bm, src // bk
    key = rb * C + cb
    uniq, inv = np.unique(key, return_inverse=True)
    urb, ucb = uniq // C, uniq % C
    counts = np.bincount(urb, minlength=R)
    W = width or max(int(counts.max(initial=1)), 1)
    if counts.max(initial=0) > W:
        raise ValueError(f"block-ELL width overflow: need {counts.max()} > {W}")

    # the bitmask is exact only for unit weights with no duplicate edges
    if storage in ("bitmask", "auto"):
        edge_key = dst * n + src
        unit = bool(np.all(w == 1.0)) and np.unique(edge_key).size == src.size
        if storage == "bitmask" and not unit:
            raise ValueError("bitmask storage requires unit weights and "
                             "no duplicate edges")
        use_mask = unit
    else:
        use_mask = False

    block_cols = np.full((R, W), -1, np.int32)
    slot_of = np.zeros(uniq.shape[0], np.int64)
    fill = np.zeros(R, np.int64)
    for i, (r, c) in enumerate(zip(urb, ucb)):
        s = fill[r]
        block_cols[r, s] = c
        slot_of[i] = s
        fill[r] += 1
    if use_mask:
        # set bits directly in packed form (MSB-first, matching unpackbits)
        # so no full (R, W, bm, bk) temporary is ever allocated
        packed = np.zeros((R, W, bm, (bk + 7) // 8), np.uint8)
        lane = src % bk
        np.bitwise_or.at(
            packed, (rb, slot_of[inv], dst % bm, lane // 8),
            (np.uint8(1) << (7 - lane % 8).astype(np.uint8)))
        return BlockEll(block_cols=block_cols, blocks=None, num_nodes=n,
                        bm=bm, bk=bk, packed=packed)
    blocks = np.zeros((R, W, bm, bk), dtype)
    np.add.at(blocks, (rb, slot_of[inv], dst % bm, src % bk), w.astype(dtype))
    return BlockEll(block_cols=block_cols, blocks=blocks, num_nodes=n,
                    bm=bm, bk=bk)


def transpose_graph(g: Graph) -> Graph:
    """Reversed-edge view of ``g`` (A -> A^T): the backward-pass adjacency."""
    return dataclasses.replace(g, src=g.dst, dst=g.src)


def traffic_model(ell: BlockEll, d: int, bytes_per_el: int = 4,
                  adj_itemsize: Optional[int] = None) -> dict:
    """HBM traffic of one block-ELL SpMM vs a pure edge-gather baseline.

    gather baseline: every edge loads a d-vector (no reuse) = nnz * d * B.
    block-ELL:       one (bk, d) tile per active block + output writes +
                     the adjacency tiles themselves, at ``adj_itemsize``
                     bytes per entry when the kernel is fed unpacked tiles,
                     else at their storage width (the implicit bitmask
                     streams 32x fewer adjacency bytes than float32).
    The ratio is the TPU analogue of the paper's off-chip traffic reduction.
    """
    stats = ell.density_stats()
    gather = stats["nnz"] * d * bytes_per_el
    if adj_itemsize is not None:
        adj_bytes = ell.n_active * ell.bm * ell.bk * adj_itemsize
    elif ell.implicit:
        adj_bytes = ell.n_active * ell.bm * ((ell.bk + 7) // 8)
    else:
        adj_bytes = ell.n_active * ell.bm * ell.bk * ell.dtype.itemsize
    blocked = (stats["active_blocks"] * ell.bk * d * bytes_per_el
               + ell.n_row_blocks * ell.bm * d * bytes_per_el
               + adj_bytes)
    return {
        "gather_bytes": int(gather),
        "blockell_bytes": int(blocked),
        "adjacency_bytes": int(adj_bytes),
        "traffic_reduction": 1.0 - blocked / max(gather, 1),
        **stats,
    }


def choose_block_shape(d: int, vmem_budget: int = 8 * 2 ** 20,
                       bytes_per_el: int = 4) -> Tuple[int, int]:
    """Static node-level mapping heuristic (paper §IV-D2): pick MXU-aligned
    (bm, bk) so the working set fits the VMEM budget.  ``exec.autotune``
    replaces this with measurement; this remains the zero-measurement prior."""
    bm = bk = 128  # MXU native
    def footprint(bm, bk):
        return (bm * bk + bk * d + bm * d) * bytes_per_el
    while footprint(bm * 2, bk) <= vmem_budget:
        bm *= 2
        if bm >= 1024:
            break
    while footprint(bm, bk * 2) <= vmem_budget:
        bk *= 2
        if bk >= 1024:
            break
    return bm, bk
