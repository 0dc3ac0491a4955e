"""Distribution-layer integration tests on a multi-device debug mesh.

Spawned in a subprocess per test module would be cleanest; instead we skip
when the session already initialized jax with 1 device (the conftest policy
keeps smoke tests single-device).  Run standalone via:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 pytest tests/test_dist_integration.py
"""
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.graph import synthesize, DatasetSpec, build_halo_plan
from repro.core import minhash_reorder, segment_aggregate
from repro.dist import (build_send_plan, halo_aggregate, allgather_aggregate,
                        distributed_decode_attention, int8_allreduce_psum,
                        topk_compress)
from repro.kernels import ref as kref

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
n = 1024
g = synthesize(DatasetSpec("t", n, 16000, 16, 4, community=0.9,
                           num_communities=8, seed=5))
g = g.permute(minhash_reorder(g))
plan = build_halo_plan(g, 8)
send = build_send_plan(plan)
x = jnp.asarray(np.random.default_rng(0).standard_normal((n, 32)
                ).astype(np.float32))
ref = segment_aggregate(x, jnp.asarray(g.src), jnp.asarray(g.dst), n)
with mesh:
    y = halo_aggregate(mesh, x, plan, send, n // 8)
assert np.allclose(np.asarray(y), np.asarray(ref), atol=1e-4), "halo mismatch"

# distributed decode vs oracle
mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(1)
B, S, H, d = 4, 256, 8, 64
q = jnp.asarray(rng.standard_normal((B, H, d)).astype(np.float32))
k = jnp.asarray(rng.standard_normal((B, S, H, d)).astype(np.float32))
v = jnp.asarray(rng.standard_normal((B, S, H, d)).astype(np.float32))
cl = jnp.asarray([100, 256, 64, 200])
with mesh2:
    out = distributed_decode_attention(mesh2, q, k, v, cl)
refd = kref.decode_attention_ref(q, k, v, cl)
assert np.allclose(np.asarray(out), np.asarray(refd), atol=1e-4), "decode"

# compression: int8 psum ~ exact psum; topk error feedback conserves mass
gvec = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
import jax
def body(gs):
    return int8_allreduce_psum(gs, "data")
with mesh:
    out = jax.shard_map(lambda s: body(s), mesh=mesh,
                        in_specs=P("data", None), out_specs=P("data", None)
                        )(jnp.tile(gvec, (8, 1))[:512])
kept, err = topk_compress(gvec, jnp.zeros_like(gvec), k_frac=0.1)
assert np.allclose(np.asarray(kept + err), np.asarray(gvec), atol=1e-6)
assert float((kept != 0).mean()) <= 0.11
print("DIST_OK")
"""


# JAX_PLATFORMS must survive into the stripped env: without it jax probes
# any installed TPU plugin (60s+ hang) before falling back to CPU.
_SUBPROC_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                "JAX_PLATFORMS": "cpu"}


@pytest.mark.slow
def test_distributed_paths():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=900, env=_SUBPROC_ENV)
    assert "DIST_OK" in r.stdout, r.stdout + r.stderr


DRYRUN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax
from repro.configs import get
from repro.launch.dryrun import lower_cell

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
# one cheap cell per family proves the whole path on a debug mesh
for arch, shape in (("gcn-cora", "molecule"), ("wide-deep", "serve_p99")):
    spec = get(arch)
    res, _, _ = lower_cell(spec.bundle(), spec, shape, mesh)
    assert res["cost"]["flops_per_device"] > 0
print("DRYRUN_OK")
"""


@pytest.mark.slow
def test_dryrun_debug_mesh():
    r = subprocess.run([sys.executable, "-c", DRYRUN_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=_SUBPROC_ENV)
    assert "DRYRUN_OK" in r.stdout, r.stdout + r.stderr


DIST_TRAIN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.dist import train_distributed

runs = {agg: train_distributed("gcn-cora", steps=3, parts=4, aggregator=agg,
                               log=lambda *_: None)
        for agg in ("halo", "allgather")}
for res in runs.values():
    for name, a in res["batch"].items():
        # node-axis inputs land sharded, never whole on one device
        assert len(a.sharding.device_set) == 4, name
        assert {s.data.shape[0] for s in a.addressable_shards} == {
            a.shape[0] // 4}, name
np.testing.assert_allclose(runs["halo"]["losses"],
                           runs["allgather"]["losses"], rtol=1e-5)
print("DIST_TRAIN_OK")
"""


@pytest.mark.slow
def test_train_distributed_shards_batch_and_matches_allgather():
    r = subprocess.run([sys.executable, "-c", DIST_TRAIN_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=_SUBPROC_ENV)
    assert "DIST_TRAIN_OK" in r.stdout, r.stdout + r.stderr
