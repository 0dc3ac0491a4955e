"""Production mesh construction (deliverable e).

Defined as FUNCTIONS so importing never touches jax device state.
Single pod: (16, 16) = 256 v5e chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
carries data parallelism whose gradient all-reduce crosses the inter-pod
links (DCI), exactly how real multi-pod jobs lay out.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for tests on a handful of host devices."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_halo_debug_mesh(parts: int | None = None):
    """1-D data mesh for the dist halo-exchange path, one shard per part.

    Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get
    N shards on CPU; defaults to every visible device.
    """
    parts = parts or jax.device_count()
    if jax.device_count() < parts:
        raise ValueError(
            f"need {parts} devices, have {jax.device_count()}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={parts}")
    return jax.make_mesh((parts,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
