"""Seconds of backend compilation during set-up, from JAX's
``/jax/core/compile/backend_compile_duration`` events."""


def read(ctx):
    return ctx["setup"].get("compile_s")
