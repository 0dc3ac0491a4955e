"""Compile the block-ELL kernels for a described TPU v5e, without the chip.

Interpret-mode parity (test_kernels.py, test_exec*.py) cannot see what the
TPU compiler refuses: block shapes off the (8, 128) tiling, casts Mosaic
does not lower, more VMEM than a kernel may use.  These tests compile every
kernel of the main path at Cora's real padded widths (the 1433-wide input
padded to 1536, the 16-wide hidden layer padded to 128) for one chip of a
``v5e:2x2`` topology, at the plans' tiles (128, 256), the TPU hub-bucket
tile (512, where the 1536-wide layer needs a raised VMEM limit) and the
small degree-bucket tiles (16, 64).  Nothing runs, so nothing here says
anything about speed.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.launch.runtime import float32_matmuls

K = importlib.import_module("repro.kernels.spmm_blockell")

N = 2708                       # Cora's nodes
LAYERS = ((1536, 128), (128, 128))
N_ACTIVE = 300                 # compacted slots (any count compiles alike)
W_SLOTS = 6                    # ELL width of the padded grid


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return spec


def _compile(fn, *args):
    """Compile at JAX's default matmul precision and at the float32
    precision the launchers run under (Mosaic's fp32 contract precision)."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    with float32_matmuls():
        exact = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in exact.as_text()
    return exact


def _operands(spec, t, d_in, d_out):
    R = C = -(-N // t)
    return dict(R=R, C=C, rows=spec((N_ACTIVE,), jnp.int32),
                cols=spec((N_ACTIVE,), jnp.int32),
                blocks=spec((N_ACTIVE, t, t), jnp.int8),
                x=spec((C * t, d_in)), s_in=spec((C * t, 1)),
                s_out=spec((R * t, 1)), w=spec((d_in, d_out)),
                b=spec((1, d_out)))


@pytest.mark.parametrize("t", [16, 64, 128, 256, 512])
@pytest.mark.parametrize("d_in,d_out", LAYERS)
def test_compact_kernels_compile(one_chip, t, d_in, d_out):
    o = _operands(_spec(one_chip), t, d_in, d_out)
    for add_diag in (False, True):
        _compile(lambda r, c, bl, x, si, so: K.spmm_blockell_compact(
            r, c, bl, x, si, so, bm=t, bk=t, n_row_blocks=o["R"],
            add_diag=add_diag),
            o["rows"], o["cols"], o["blocks"], o["x"], o["s_in"], o["s_out"])
        _compile(lambda r, c, bl, x, si, so, w, b:
                 K.spmm_blockell_update_compact(
                     r, c, bl, x, si, so, w, b, bm=t, bk=t,
                     n_row_blocks=o["R"], add_diag=add_diag, relu=True),
                 o["rows"], o["cols"], o["blocks"], o["x"], o["s_in"],
                 o["s_out"], o["w"], o["b"])


@pytest.mark.parametrize("t", [128, 256])
@pytest.mark.parametrize("d_in,d_out", LAYERS)
def test_padded_kernels_compile(one_chip, t, d_in, d_out):
    spec = _spec(one_chip)
    o = _operands(spec, t, d_in, d_out)
    cols = spec((o["R"], W_SLOTS), jnp.int32)
    blocks = spec((o["R"], W_SLOTS, t, t), jnp.int8)
    for add_diag in (False, True):
        _compile(lambda c, bl, x, si, so: K.spmm_blockell_fused(
            c, bl, x, si, so, bm=t, bk=t, add_diag=add_diag),
            cols, blocks, o["x"], o["s_in"], o["s_out"])
    # two-W epilogue with the traced SMEM self coefficient
    _compile(lambda c, bl, x, si, so, w, b, ws, cf: K.spmm_blockell_update(
        c, bl, x, si, so, w, b, ws, cf, bm=t, bk=t, add_diag=True,
        relu=True),
        cols, blocks, o["x"], o["s_in"], o["s_out"], o["w"], o["b"],
        o["w"], spec((1, 1)))


@pytest.mark.parametrize("t", [16, 64])
def test_bucketed_operands_compile(one_chip, t):
    """A degree bucket's sub-grid: destination-row operands (``x_diag``,
    ``s_in_diag``, ``x_self``) in bucket-local order, global sources."""
    spec = _spec(one_chip)
    d_in, d_out = LAYERS[0]
    o = _operands(spec, t, d_in, d_out)
    R = -(-300 // t)                   # a 300-node bucket
    xg, sg = spec((R * t, d_in)), spec((R * t, 1))
    _compile(lambda r, c, bl, x, si, so, xd, sd: K.spmm_blockell_compact(
        r, c, bl, x, si, so, xd, sd, bm=t, bk=t, n_row_blocks=R,
        add_diag=True),
        o["rows"], o["cols"], o["blocks"], o["x"], o["s_in"],
        spec((R * t, 1)), xg, sg)
    _compile(lambda r, c, bl, x, si, so, w, b, ws, cf, xs, xd, sd:
             K.spmm_blockell_update_compact(
                 r, c, bl, x, si, so, w, b, ws, cf, x_self=xs, x_diag=xd,
                 s_in_diag=sd, bm=t, bk=t, n_row_blocks=R, add_diag=True,
                 relu=True),
             o["rows"], o["cols"], o["blocks"], o["x"], o["s_in"],
             spec((R * t, 1)), o["w"], o["b"], o["w"], spec((1, 1)), xg, xg,
             sg)


def test_fused_layer_plan_compiles(one_chip, cora):
    """A whole fused GCN layer plan (1433 -> 16, compact tile 128) compiles
    forward and backward: the layer kernel, the transpose plan's
    aggregation kernel and the fallback-row glue around them."""
    from repro.exec import build_layer_plan
    spec = _spec(one_chip)
    lp = build_layer_plan(cora, "gcn", d_in=1433, d_out=16,
                          order="aggregate_first", fuse=True, bm=128,
                          backend="pallas", interpret=False)

    def fwd_bwd(x, w, b):
        y, vjp = jax.vjp(lambda x, w, b: lp.apply(x, w, b, relu=True),
                         x, w, b)
        return vjp(y)

    compiled = _compile(fwd_bwd, spec((N, 1433)), spec((1433, 16)),
                        spec((16,)))
    mem = compiled.memory_analysis()
    if mem is not None:
        assert mem.temp_size_in_bytes < 16e9
    assert np.isfinite(lp.gplan.describe(1433)["plan_bytes"])
