"""Compile the gcn-arxiv training step at full size for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse_arxiv.py

Nothing runs and no chip is needed: the TPU compiler, installed beside JAX,
compiles the step the ``gcn-arxiv.train`` cell times (``make_train_step``
over ``GNNBundle("gcn").loss_fn`` with the segment executor and Adam) for
one chip of a described ``v5e:2x2`` and prints ``memory_analysis()``: the
bytes the step needs on the device.  A step that does not fit, or that the
compiler refuses, fails here without chip time.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main() -> None:
    harness.add_program_path()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs.families import GNNBundle
    from repro.launch.runtime import float32_matmuls
    from repro.train.loop import make_train_step
    from repro.train.optimizer import adam

    jax.config.update("jax_enable_compilation_cache", False)
    spec = harness.load_spec()
    cfg = harness.config(spec, "gcn-arxiv")
    gspec = cfg["graph"]
    n, e = gspec["num_nodes"], gspec["num_edges"]
    dims = (gspec["feat_dim"], *cfg["hidden"], cfg["classes"])

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    bundle = GNNBundle("gcn", {"hidden": list(cfg["hidden"])},
                       n_classes=cfg["classes"])
    opt = adam(cfg["optimizer"]["lr"])
    step = make_train_step(bundle.loss_fn("full_graph_sm",
                                          executor=cfg["executor"]),
                           opt, clip_norm=cfg["optimizer"]["clip_norm"])
    params = {"layers": [{"w": sds((a, b), jnp.float32),
                          "b": sds((b,), jnp.float32)}
                         for a, b in zip(dims[:-1], dims[1:])]}
    opt_state = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype), jax.eval_shape(opt.init, params))
    batch = {"src": sds((e,), jnp.int32), "dst": sds((e,), jnp.int32),
             "edge_mask": sds((e,), jnp.bool_),
             "labels": sds((n,), jnp.int32),
             "train_mask": sds((n,), jnp.bool_),
             "x": sds((n, dims[0]), jnp.float32),
             "deg": sds((n,), jnp.float32)}
    with float32_matmuls():
        compiled = step.lower(params, opt_state, batch).compile()
    mem = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes")
    print(json.dumps({"cell": "gcn-arxiv.train", "target": "v5e:2x2, one chip",
                      **{f: getattr(mem, f) for f in fields}}))


if __name__ == "__main__":
    main()
