"""Sharding vocabulary shared by models, bundles, and the launch layer.

Everything here is mesh-OPTIONAL: on a single device (unit tests, smoke
configs) ``ambient_mesh()`` is None and every helper degrades to identity, so
model code can sprinkle sharding hints unconditionally.  Under ``with mesh:``
the same hints become real ``with_sharding_constraint`` annotations.

Conventions (mirrors launch/mesh.py):
  * batch/data parallelism lives on the ``data`` axis (plus ``pod`` when the
    multi-pod mesh is in play) — ``batch_axes(mesh)`` resolves the tuple;
  * tensor/expert parallelism lives on the ``model`` axis;
  * LM parameter stacks carry a leading layer axis which is ZeRO-sharded over
    the batch axes; ``make_constrain`` in families.py drops that leading entry
    to re-assert the per-layer (model-axis) sharding inside scan bodies.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ------------------------------------------------------------ ambient mesh
def ambient_mesh() -> Optional[Mesh]:
    """The mesh installed by ``with mesh:``, or None outside any context."""
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
    except Exception:
        return None
    if m is None or m.empty:
        return None
    return m


def batch_axes(mesh: Mesh):
    """Mesh axes carrying batch/data parallelism, innermost last.

    Returns a bare axis name when only one qualifies (reads better in specs)
    and a tuple when the multi-pod mesh contributes ``pod`` as well.
    """
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if len(axes) == 1:
        return axes[0]
    return axes


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        return mesh.shape.get(entry, 1)
    size = 1
    for a in entry:
        size *= mesh.shape.get(a, 1)
    return size


def _resolve_entry(mesh: Mesh, entry, dim: int):
    """Map one spec entry onto the mesh; drop it if absent or non-dividing."""
    if entry == "batch":
        entry = batch_axes(mesh)
    if isinstance(entry, str):
        entry = (entry,)
    if entry is None:
        return None
    kept = tuple(a for a in entry if mesh.shape.get(a, 1) > 1)
    if not kept:
        return None
    size = _axis_size(mesh, kept)
    if dim % size != 0:
        return None
    return kept if len(kept) > 1 else kept[0]


def activation_spec(mesh: Mesh, axes: Sequence[Any], shape) -> P:
    """Resolve an abstract activation layout (``"batch"``/axis-name/None per
    dim) into a concrete PartitionSpec valid on ``mesh`` for ``shape``."""
    return P(*(_resolve_entry(mesh, a, d) for a, d in zip(axes, shape)))


def shard_activation(x: jax.Array, axes: Sequence[Any]) -> jax.Array:
    """Constrain ``x`` to the given layout under the ambient mesh (identity
    when no mesh is installed — the single-device test path)."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    spec = activation_spec(mesh, axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    """``with_sharding_constraint`` iff an ambient mesh exists and ``spec``
    is realizable on it (absent axes / non-dividing dims are dropped)."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    entries = tuple(spec) + (None,) * (x.ndim - len(tuple(spec)))
    resolved = activation_spec(mesh, entries, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, resolved))


def to_shardings(mesh: Mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


# ------------------------------------------------------ LM parameter specs
def _mdl(mesh: Mesh, dim: int):
    """The model axis, if present and dividing ``dim``; else replicate."""
    if "model" in mesh.axis_names and mesh.shape["model"] > 1 \
            and dim % mesh.shape["model"] == 0:
        return "model"
    return None


def lm_param_specs(cfg, mesh: Mesh):
    """PartitionSpec tree for the stacked LM parameter pytree (lm_init).

    Layout: tensor parallelism on ``model`` (column-parallel wq/wk/wv/wg/wu,
    row-parallel wo/wd, expert-parallel MoE stacks when E divides the model
    axis), ZeRO over the batch axes on the leading LAYER-STACK axis.  The
    structure intentionally uses single-P leaves for uniform sub-pytrees
    (linear {"w"}, rmsnorm {"scale"}) — consumers broadcast them.
    """
    ba = batch_axes(mesh)
    zb = ba  # ZeRO shard of the layer stack axis
    d, hd = cfg.d_model, cfg.hd
    qout, kvout = cfg.n_heads * hd, cfg.n_kv * hd

    def attn_specs():
        return {"wq": P(zb, None, _mdl(mesh, qout)),
                "wk": P(zb, None, _mdl(mesh, kvout)),
                "wv": P(zb, None, _mdl(mesh, kvout)),
                "wo": P(zb, _mdl(mesh, qout), None)}

    def layer_common():
        return {"attn": attn_specs(), "ln1": P(zb, None), "ln2": P(zb, None)}

    specs = {
        "embed": P(_mdl(mesh, cfg.vocab), None),
        "ln_f": P(None),
        "head": P(None, _mdl(mesh, cfg.vocab)),
    }
    f = cfg.d_ff
    if cfg.n_experts:
        mdl_sz = mesh.shape.get("model", 1)
        moe = layer_common()
        if mdl_sz > 1 and cfg.n_experts % mdl_sz == 0:
            # expert parallelism: whole experts per model shard
            ew = P(zb, "model", None, None)
            moe["moe"] = {"router": P(zb, None, None),
                          "wg": ew, "wu": ew, "wd": ew}
        else:
            # tensor parallelism inside each expert
            moe["moe"] = {"router": P(zb, None, None),
                          "wg": P(zb, None, None, _mdl(mesh, f)),
                          "wu": P(zb, None, None, _mdl(mesh, f)),
                          "wd": P(zb, None, _mdl(mesh, f), None)}
        if cfg.shared_expert:
            moe["moe"]["shared"] = {"wg": P(zb, None, _mdl(mesh, f)),
                                    "wu": P(zb, None, _mdl(mesh, f)),
                                    "wd": P(zb, _mdl(mesh, f), None)}
        specs["moe_layers"] = moe
        if cfg.n_dense_layers:
            dense = layer_common()
            dense["ffn"] = {"wg": P(zb, None, _mdl(mesh, f)),
                            "wu": P(zb, None, _mdl(mesh, f)),
                            "wd": P(zb, _mdl(mesh, f), None)}
            specs["dense_layers"] = dense
    else:
        dense = layer_common()
        dense["ffn"] = {"wg": P(zb, None, _mdl(mesh, f)),
                        "wu": P(zb, None, _mdl(mesh, f)),
                        "wd": P(zb, _mdl(mesh, f), None)}
        specs["dense_layers"] = dense
    return specs
