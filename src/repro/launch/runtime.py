"""Process-wide JAX set-up shared by the launchers.

``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before they compile anything and run their work
inside :func:`float32_matmuls`; importing ``repro`` does neither.

Compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that
variable itself and no other directory is set here.  Otherwise the cache
lives at one fixed path in the checkout, ``<repo>/.jax_cache`` (gitignored):
the directory is part of what a cached entry is found by, so a path that
moved between runs would never hit.

Matmul precision: every model here keeps float32 parameters and activations,
and its checks (the serving oracle, the backend races of ``repro.exec``)
compare engines at 1e-4.  On a TPU a float32 matmul at JAX's default
precision rounds its operands to bfloat16, so two engines that order the
same sum differently disagree by ~1e-3.  The launchers therefore run float32
matmuls at float32 precision, in XLA and in the Pallas kernels alike; on a
CPU this changes nothing.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def float32_matmuls():
    """Context in which float32 matmuls (XLA and Pallas) run at float32
    precision rather than the TPU default of one bfloat16 pass."""
    return jax.default_matmul_precision("float32")
