"""Block-ELL SpMM Pallas kernels — Rubik's aggregation engine on TPU.

y = A @ x with A block-sparse in ELL format (see core/blocksparse.py).  After
LSH reordering the adjacency concentrates near the diagonal, so each
destination block touches few source blocks; these kernels

  * stream one (bk, d) source-feature tile from HBM into VMEM per ACTIVE
    block and reuse it across the whole (bm) destination tile — the
    explicitly-managed analogue of the paper's per-PE G-D cache;
  * run the per-block (bm, bk) x (bk, d) product on the MXU
    (128-aligned tiles, fp32 accumulation);
  * use scalar prefetch (PrefetchScalarGridSpec) so the x-tile index map
    reads the ELL column table — the canonical Pallas gather pattern.

Three variants:

``spmm_blockell``          — the original padded kernel: grid (R, W),
    predicated-skip of inactive slots (col == -1) with @pl.when.  Padding
    slots cost a control step but no FLOPs.
``spmm_blockell_fused``    — padded grid plus *fused symmetric scaling*:
    computes  s_out ⊙ (A (s_in ⊙ x) [+ s_in ⊙ x])  in one launch.  The
    scaling vectors live in VMEM tiles; the optional self-loop diagonal is
    handled in the accumulator's init step, so a whole GCN
    scale → SpMM → add-loop → scale chain is one kernel.
``spmm_blockell_compact``  — the *slot-compacted* fused kernel: the grid
    iterates only the ``n_active`` live blocks via scalar-prefetched
    row-major-sorted (row, col) lists.  Skewed graphs whose hub rows inflate
    the ELL width W no longer tax every other row with padded control steps;
    the grid is exactly ``n_active`` (tests assert this).  Because the slot
    list is row-major sorted, each output block is revisited on consecutive
    steps only, so Pallas keeps the accumulator resident in VMEM.

``spmm_blockell_update`` / ``spmm_blockell_update_compact`` — the *layer*
    kernels (hierarchical fusion, ISSUE 4): the graph-level aggregation
    accumulates into a VMEM **scratch** tile and, on each destination block's
    last slot, the epilogue multiplies the accumulated (bm, d_in) tile by the
    resident update matrix ``W`` (d_in, d_out) on the MXU — optionally adding
    bias and applying ReLU — before the single (bm, d_out) store.  A whole
    GCN layer  relu(s_out ⊙ (A (s_in ⊙ x) [+ s_in ⊙ x]) @ W + b)  becomes ONE
    launch: the (n, d_in) aggregation result never round-trips through HBM.

    The epilogue generalizes to a TWO-W form (ISSUE 5): with ``w_self`` the
    destination-row tile of x joins the update on the MXU,

        out = (s_out ⊙ acc) @ W_nbr + (self_coeff ⊙ x_tile) @ W_self + b,

    where ``self_coeff`` is an optional (1, 1) SMEM scalar operand (a traced
    model parameter, not a compile-time constant).  GraphSAGE's concat form
    ``concat(h, F(h)) @ W == h @ W_self + F(h) @ W_nbr`` and GIN's
    ``((1+ε) h + F(h)) @ W`` (pass ``w_self = w`` and ``self_coeff = 1+ε``)
    each become one launch per layer.

Destination blocks with zero active slots are never visited by the compacted
grids; callers (repro.exec) fill those rows from the analytic diagonal term.

Operand layouts the TPU compiler (Mosaic) accepts at every tile the plans
use (16 to 512): the per-node scale vectors are (C*bk, 1) columns read
through (bk, 1) blocks, so a block's last dimension equals the array's and
its row count is a multiple of 8.  Unit-weight adjacency tiles arrive as
int8 (Mosaic casts int8 but not uint8 to float32); weighted tiles as
float32.  Kernels whose double-buffered blocks outgrow the default scoped
VMEM (the 1536-wide first GCN layer at tile 512) raise the limit.

Degree-binned multi-grid use (ISSUE 9): ``repro.exec.bucketing`` partitions
destination NODES by in-degree and launches one compact kernel per bucket,
each with its own square (bm, bk) tile.  A bucket's destination rows are
remapped to a contiguous local space while sources stay global, so the
destination-row-indexed operands (the ``add_diag`` self-term tiles and the
two-W ``x_self`` tile) no longer live at ``rows[i]`` inside the global x —
the compact kernels therefore accept optional separable destination
operands (``x_diag`` / ``s_in_diag`` / ``x_self``): bucket-local gathered
arrays substituted into the same operand slots.  Kernel bodies are
unchanged; a single identity bucket is bit-identical to the unbucketed
kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_DEFAULT = 16 * 2 ** 20     # Mosaic's default scoped-VMEM limit
_VMEM_CAP = 96 * 2 ** 20         # under the 128 MiB of a v5e core


def _block_bytes(shape) -> int:
    """VMEM bytes of one buffer of a block: the last two dims padded to the
    (8, 128) tile at 4 bytes per element (an upper bound for int8)."""
    shape = (1,) * (2 - len(shape)) + tuple(shape)
    rows, lanes = -(-shape[-2] // 8) * 8, -(-shape[-1] // 128) * 128
    return math.prod(shape[:-2]) * rows * lanes * 4


def _call(kernel, grid_spec, out_shape, name: str, interpret: bool,
          *operands):
    """``pallas_call`` with a stable kernel name and, where the
    double-buffered in/out blocks plus scratch outgrow the default scoped
    VMEM, a raised ``vmem_limit_bytes``."""
    blocks = [s.block_shape for s in grid_spec.in_specs]
    blocks.append(grid_spec.out_specs.block_shape)
    need = (2 * sum(_block_bytes(b) for b in blocks)
            + sum(_block_bytes(s.shape) for s in grid_spec.scratch_shapes))
    params = None
    if need > _VMEM_DEFAULT * 3 // 4:
        params = pltpu.CompilerParams(
            vmem_limit_bytes=min(2 * need, _VMEM_CAP))
    return pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          name=name, compiler_params=params,
                          interpret=interpret)(*operands)


def _kernel(cols_ref, adj_ref, x_ref, o_ref):
    r = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(cols_ref[r, w] >= 0)
    def _accum():
        o_ref[...] += jnp.dot(adj_ref[0, 0], x_ref[...],
                              preferred_element_type=jnp.float32
                              ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "interpret"))
def spmm_blockell(block_cols: jax.Array, blocks: jax.Array, x: jax.Array,
                  *, bm: int, bk: int, interpret: bool = False) -> jax.Array:
    """block_cols: (R, W) int32 (-1 = inactive); blocks: (R, W, bm, bk);
    x: (C*bk, d) with d a multiple of 128 (ops.py pads).  Returns (R*bm, d).
    """
    R, W = block_cols.shape
    d = x.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, W),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bk), lambda r, w, cols: (r, w, 0, 0)),
            pl.BlockSpec((bk, d),
                         lambda r, w, cols: (jnp.maximum(cols[r, w], 0), 0)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda r, w, cols: (r, 0)),
    )
    return _call(_kernel, grid_spec, jax.ShapeDtypeStruct((R * bm, d), x.dtype),
                 "spmm_blockell", interpret, block_cols, blocks, x)


# ---------------------------------------------------------------------------
# fused padded kernel: s_out ⊙ (A (s_in ⊙ x) [+ s_in ⊙ x]) in one launch
# ---------------------------------------------------------------------------
def _make_fused_kernel(W: int, add_diag: bool):
    def kernel(cols_ref, adj_ref, x_ref, sin_ref, sout_ref, *rest):
        if add_diag:
            xd_ref, sind_ref, o_ref = rest
        else:
            (o_ref,) = rest
        r = pl.program_id(0)
        w = pl.program_id(1)

        @pl.when(w == 0)
        def _init():
            if add_diag:
                o_ref[...] = xd_ref[...] * sind_ref[...]
            else:
                o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(cols_ref[r, w] >= 0)
        def _accum():
            xs = x_ref[...] * sin_ref[...]
            o_ref[...] += jnp.dot(adj_ref[0, 0].astype(jnp.float32), xs,
                                  preferred_element_type=jnp.float32
                                  ).astype(o_ref.dtype)

        @pl.when(w == W - 1)
        def _scale():
            o_ref[...] *= sout_ref[...]
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "add_diag", "interpret"))
def spmm_blockell_fused(block_cols: jax.Array, blocks: jax.Array,
                        x: jax.Array, s_in: jax.Array, s_out: jax.Array,
                        *, bm: int, bk: int, add_diag: bool,
                        interpret: bool = False) -> jax.Array:
    """Padded fused SpMM.  s_in: (C*bk, 1); s_out: (R*bm, 1); x: (C*bk, d).
    With ``add_diag`` (requires bm == bk so a row tile of x is a block tile)
    the self-loop term s_in ⊙ x seeds the accumulator.  Returns (R*bm, d).
    """
    R, W = block_cols.shape
    d = x.shape[1]
    if add_diag and bm != bk:
        raise ValueError("add_diag requires square blocks (bm == bk)")
    in_specs = [
        pl.BlockSpec((1, 1, bm, bk), lambda r, w, cols: (r, w, 0, 0)),
        pl.BlockSpec((bk, d),
                     lambda r, w, cols: (jnp.maximum(cols[r, w], 0), 0)),
        pl.BlockSpec((bk, 1),
                     lambda r, w, cols: (jnp.maximum(cols[r, w], 0), 0)),
        pl.BlockSpec((bm, 1), lambda r, w, cols: (r, 0)),
    ]
    operands = [blocks, x, s_in, s_out]
    if add_diag:
        in_specs += [pl.BlockSpec((bk, d), lambda r, w, cols: (r, 0)),
                     pl.BlockSpec((bk, 1), lambda r, w, cols: (r, 0))]
        operands += [x, s_in]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, W),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, d), lambda r, w, cols: (r, 0)),
    )
    return _call(_make_fused_kernel(W, add_diag), grid_spec,
                 jax.ShapeDtypeStruct((R * bm, d), x.dtype),
                 "spmm_blockell_fused", interpret, block_cols, *operands)


# ---------------------------------------------------------------------------
# slot-compacted fused kernel: grid = (n_active,), no padded control steps
# ---------------------------------------------------------------------------
def _make_compact_kernel(n_active: int, add_diag: bool):
    def kernel(rows_ref, cols_ref, adj_ref, x_ref, sin_ref, sout_ref, *rest):
        if add_diag:
            xd_ref, sind_ref, o_ref = rest
        else:
            (o_ref,) = rest
        i = pl.program_id(0)
        r = rows_ref[i]
        first = (i == 0) | (rows_ref[jnp.maximum(i - 1, 0)] != r)
        last = ((i == n_active - 1)
                | (rows_ref[jnp.minimum(i + 1, n_active - 1)] != r))

        @pl.when(first)
        def _init():
            if add_diag:
                o_ref[...] = xd_ref[...] * sind_ref[...]
            else:
                o_ref[...] = jnp.zeros_like(o_ref)

        xs = x_ref[...] * sin_ref[...]
        o_ref[...] += jnp.dot(adj_ref[0].astype(jnp.float32), xs,
                              preferred_element_type=jnp.float32
                              ).astype(o_ref.dtype)

        @pl.when(last)
        def _scale():
            o_ref[...] *= sout_ref[...]
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "n_row_blocks", "add_diag",
                                    "interpret"))
def spmm_blockell_compact(rows: jax.Array, cols: jax.Array,
                          blocks: jax.Array, x: jax.Array,
                          s_in: jax.Array, s_out: jax.Array,
                          x_diag: jax.Array = None,
                          s_in_diag: jax.Array = None,
                          *, bm: int, bk: int, n_row_blocks: int,
                          add_diag: bool, interpret: bool = False
                          ) -> jax.Array:
    """Slot-compacted fused SpMM: the grid is exactly ``n_active`` steps.

    rows / cols: (n_active,) int32 sorted row-major (core.BlockCompaction);
    blocks: (n_active, bm, bk); x: (C*bk, d); s_in: (C*bk, 1);
    s_out: (R*bm, 1).
    ``x_diag`` (R*bm, d) / ``s_in_diag`` (R*bm, 1) override the ``add_diag``
    self-term operands when destination rows are remapped (degree-bucketed
    sub-grids); default is the unbucketed behavior where destination row
    tiles are slices of the global x / s_in.
    Returns (R*bm, d); rows whose destination block has no active slot are
    left unwritten — repro.exec fills them with the diagonal fallback.
    """
    n_active = rows.shape[0]
    R = n_row_blocks
    d = x.shape[1]
    if add_diag and bm != bk:
        raise ValueError("add_diag requires square blocks (bm == bk)")
    if n_active == 0:
        raise ValueError("empty compaction; caller handles n_active == 0")
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda i, rows, cols: (i, 0, 0)),
        pl.BlockSpec((bk, d), lambda i, rows, cols: (cols[i], 0)),
        pl.BlockSpec((bk, 1), lambda i, rows, cols: (cols[i], 0)),
        pl.BlockSpec((bm, 1), lambda i, rows, cols: (rows[i], 0)),
    ]
    operands = [blocks, x, s_in, s_out]
    if add_diag:
        in_specs += [pl.BlockSpec((bk, d), lambda i, rows, cols: (rows[i], 0)),
                     pl.BlockSpec((bk, 1), lambda i, rows, cols: (rows[i], 0))]
        operands += [x if x_diag is None else x_diag,
                     s_in if s_in_diag is None else s_in_diag]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_active,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, d), lambda i, rows, cols: (rows[i], 0)),
    )
    return _call(_make_compact_kernel(n_active, add_diag), grid_spec,
                 jax.ShapeDtypeStruct((R * bm, d), x.dtype),
                 "spmm_blockell_compact", interpret, rows, cols, *operands)


# ---------------------------------------------------------------------------
# layer kernels: SpMM + node-level update (W, bias, ReLU) in one launch
# ---------------------------------------------------------------------------
def _layer_epilogue(acc_ref, sout_ref, w_ref, bias_ref, o_ref, relu,
                    xself_ref=None, wself_ref=None, coeff_ref=None):
    """Shared epilogue: scale the accumulated tile, multiply by the resident
    W tile on the MXU, add bias, apply ReLU — all in VMEM, then one store.
    With ``wself_ref`` the destination-row x tile contributes a second MXU
    product (optionally scaled by the SMEM ``self_coeff`` scalar):
    two-W form  out = (s_out ⊙ acc) @ W_nbr + (c ⊙ x_tile) @ W_self + b."""
    y = acc_ref[...] * sout_ref[...]
    out = jnp.dot(y, w_ref[...], preferred_element_type=jnp.float32)
    if wself_ref is not None:
        xs = xself_ref[...]
        if coeff_ref is not None:
            xs = xs * coeff_ref[0, 0]
        out = out + jnp.dot(xs, wself_ref[...],
                            preferred_element_type=jnp.float32)
    if bias_ref is not None:
        out = out + bias_ref[0][None, :]
    if relu:
        out = jnp.maximum(out, 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


def _make_update_kernel(n_slots: int, add_diag: bool, has_bias: bool,
                        relu: bool, has_self: bool = False,
                        has_coeff: bool = False):
    def kernel(cols_ref, adj_ref, x_ref, sin_ref, sout_ref, w_ref, *rest):
        rest = list(rest)
        bias_ref = rest.pop(0) if has_bias else None
        wself_ref = rest.pop(0) if has_self else None
        xself_ref = rest.pop(0) if has_self else None
        coeff_ref = rest.pop(0) if has_coeff else None
        if add_diag:
            xd_ref, sind_ref = rest.pop(0), rest.pop(0)
        o_ref, acc_ref = rest
        r = pl.program_id(0)
        w = pl.program_id(1)

        @pl.when(w == 0)
        def _init():
            if add_diag:
                acc_ref[...] = xd_ref[...] * sind_ref[...]
            else:
                acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(cols_ref[r, w] >= 0)
        def _accum():
            xs = x_ref[...] * sin_ref[...]
            acc_ref[...] += jnp.dot(adj_ref[0, 0].astype(jnp.float32), xs,
                                    preferred_element_type=jnp.float32)

        @pl.when(w == n_slots - 1)
        def _update():
            _layer_epilogue(acc_ref, sout_ref, w_ref, bias_ref, o_ref, relu,
                            xself_ref, wself_ref, coeff_ref)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "add_diag", "relu",
                                    "interpret"))
def spmm_blockell_update(block_cols: jax.Array, blocks: jax.Array,
                         x: jax.Array, s_in: jax.Array, s_out: jax.Array,
                         w: jax.Array, bias, w_self=None, self_coeff=None,
                         *, bm: int, bk: int,
                         add_diag: bool, relu: bool = False,
                         interpret: bool = False) -> jax.Array:
    """Padded fused LAYER: aggregation epilogue-multiplied by ``w`` in VMEM.

    x: (C*bk, d_in); w: (d_in, d_out); bias: (1, d_out) or None; d_in and
    d_out multiples of 128 (repro.exec pads).  The aggregation accumulates in
    a VMEM scratch tile; only the (bm, d_out) updated tile is ever stored.
    ``w_self`` (d_in, d_out) adds the two-W self term — the destination-row
    x tile joins the epilogue, scaled by the traced (1, 1) ``self_coeff``
    SMEM scalar when given (requires square blocks so the row tile aligns).
    Returns (R*bm, d_out).
    """
    R, W = block_cols.shape
    d_in, d_out = w.shape
    if add_diag and bm != bk:
        raise ValueError("add_diag requires square blocks (bm == bk)")
    if w_self is not None and bm != bk:
        raise ValueError("w_self requires square blocks (bm == bk)")
    if self_coeff is not None and w_self is None:
        raise ValueError("self_coeff needs w_self")
    in_specs = [
        pl.BlockSpec((1, 1, bm, bk), lambda r, s, cols: (r, s, 0, 0)),
        pl.BlockSpec((bk, d_in),
                     lambda r, s, cols: (jnp.maximum(cols[r, s], 0), 0)),
        pl.BlockSpec((bk, 1),
                     lambda r, s, cols: (jnp.maximum(cols[r, s], 0), 0)),
        pl.BlockSpec((bm, 1), lambda r, s, cols: (r, 0)),
        pl.BlockSpec((d_in, d_out), lambda r, s, cols: (0, 0)),
    ]
    operands = [blocks, x, s_in, s_out, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, d_out), lambda r, s, cols: (0, 0)))
        operands.append(bias)
    if w_self is not None:
        in_specs += [pl.BlockSpec((d_in, d_out), lambda r, s, cols: (0, 0)),
                     pl.BlockSpec((bk, d_in), lambda r, s, cols: (r, 0))]
        operands += [w_self, x]
        if self_coeff is not None:
            in_specs.append(pl.BlockSpec((1, 1), lambda r, s, cols: (0, 0),
                                         memory_space=pltpu.SMEM))
            operands.append(self_coeff)
    if add_diag:
        in_specs += [pl.BlockSpec((bk, d_in), lambda r, s, cols: (r, 0)),
                     pl.BlockSpec((bk, 1), lambda r, s, cols: (r, 0))]
        operands += [x, s_in]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, W),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, d_out), lambda r, s, cols: (r, 0)),
        scratch_shapes=[pltpu.VMEM((bm, d_in), jnp.float32)],
    )
    return _call(_make_update_kernel(W, add_diag, bias is not None, relu,
                                     w_self is not None,
                                     self_coeff is not None),
                 grid_spec, jax.ShapeDtypeStruct((R * bm, d_out), x.dtype),
                 "spmm_blockell_update", interpret, block_cols, *operands)


def _make_update_compact_kernel(n_active: int, add_diag: bool, has_bias: bool,
                                relu: bool, has_self: bool = False,
                                has_coeff: bool = False):
    def kernel(rows_ref, cols_ref, adj_ref, x_ref, sin_ref, sout_ref, w_ref,
               *rest):
        rest = list(rest)
        bias_ref = rest.pop(0) if has_bias else None
        wself_ref = rest.pop(0) if has_self else None
        xself_ref = rest.pop(0) if has_self else None
        coeff_ref = rest.pop(0) if has_coeff else None
        if add_diag:
            xd_ref, sind_ref = rest.pop(0), rest.pop(0)
        o_ref, acc_ref = rest
        i = pl.program_id(0)
        r = rows_ref[i]
        first = (i == 0) | (rows_ref[jnp.maximum(i - 1, 0)] != r)
        last = ((i == n_active - 1)
                | (rows_ref[jnp.minimum(i + 1, n_active - 1)] != r))

        @pl.when(first)
        def _init():
            if add_diag:
                acc_ref[...] = xd_ref[...] * sind_ref[...]
            else:
                acc_ref[...] = jnp.zeros_like(acc_ref)

        xs = x_ref[...] * sin_ref[...]
        acc_ref[...] += jnp.dot(adj_ref[0].astype(jnp.float32), xs,
                                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _update():
            _layer_epilogue(acc_ref, sout_ref, w_ref, bias_ref, o_ref, relu,
                            xself_ref, wself_ref, coeff_ref)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "n_row_blocks", "add_diag",
                                    "relu", "interpret"))
def spmm_blockell_update_compact(rows: jax.Array, cols: jax.Array,
                                 blocks: jax.Array, x: jax.Array,
                                 s_in: jax.Array, s_out: jax.Array,
                                 w: jax.Array, bias, w_self=None,
                                 self_coeff=None, x_self=None,
                                 x_diag=None, s_in_diag=None,
                                 *, bm: int, bk: int,
                                 n_row_blocks: int, add_diag: bool,
                                 relu: bool = False,
                                 interpret: bool = False) -> jax.Array:
    """Slot-compacted fused LAYER: grid is exactly ``n_active`` steps and each
    destination block's last step runs the W-update epilogue before its one
    (bm, d_out) store.  ``w_self``/``self_coeff`` add the two-W self term
    exactly as in :func:`spmm_blockell_update`.  ``x_self`` (R*bm, d_in) /
    ``x_diag`` (R*bm, d_in) / ``s_in_diag`` (R*bm, 1) override the
    destination-row-indexed operands for degree-bucketed sub-grids whose
    destination rows are remapped; defaults slice the global x / s_in.
    Rows whose destination block has no active slot are left unwritten —
    repro.exec fills them with the diagonal/self-term update.
    """
    n_active = rows.shape[0]
    R = n_row_blocks
    d_in, d_out = w.shape
    if add_diag and bm != bk:
        raise ValueError("add_diag requires square blocks (bm == bk)")
    if w_self is not None and bm != bk:
        raise ValueError("w_self requires square blocks (bm == bk)")
    if self_coeff is not None and w_self is None:
        raise ValueError("self_coeff needs w_self")
    if n_active == 0:
        raise ValueError("empty compaction; caller handles n_active == 0")
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda i, rows, cols: (i, 0, 0)),
        pl.BlockSpec((bk, d_in), lambda i, rows, cols: (cols[i], 0)),
        pl.BlockSpec((bk, 1), lambda i, rows, cols: (cols[i], 0)),
        pl.BlockSpec((bm, 1), lambda i, rows, cols: (rows[i], 0)),
        pl.BlockSpec((d_in, d_out), lambda i, rows, cols: (0, 0)),
    ]
    operands = [blocks, x, s_in, s_out, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, d_out),
                                     lambda i, rows, cols: (0, 0)))
        operands.append(bias)
    if w_self is not None:
        in_specs += [pl.BlockSpec((d_in, d_out),
                                  lambda i, rows, cols: (0, 0)),
                     pl.BlockSpec((bk, d_in),
                                  lambda i, rows, cols: (rows[i], 0))]
        operands += [w_self, x if x_self is None else x_self]
        if self_coeff is not None:
            in_specs.append(pl.BlockSpec((1, 1),
                                         lambda i, rows, cols: (0, 0),
                                         memory_space=pltpu.SMEM))
            operands.append(self_coeff)
    if add_diag:
        in_specs += [pl.BlockSpec((bk, d_in),
                                  lambda i, rows, cols: (rows[i], 0)),
                     pl.BlockSpec((bk, 1), lambda i, rows, cols: (rows[i], 0))]
        operands += [x if x_diag is None else x_diag,
                     s_in if s_in_diag is None else s_in_diag]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_active,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, d_out), lambda i, rows, cols: (rows[i], 0)),
        scratch_shapes=[pltpu.VMEM((bm, d_in), jnp.float32)],
    )
    return _call(_make_update_compact_kernel(n_active, add_diag,
                                             bias is not None, relu,
                                             w_self is not None,
                                             self_coeff is not None),
                 grid_spec, jax.ShapeDtypeStruct((R * bm, d_out), x.dtype),
                 "spmm_blockell_update_compact", interpret, rows, cols,
                 *operands)
