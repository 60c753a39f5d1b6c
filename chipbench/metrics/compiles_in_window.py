"""Executables JAX obtained (compiled, or loaded from the persistent
cache) inside the measured window: each is a
``/jax/core/compile/backend_compile_duration`` event."""


def read(ctx):
    return float(ctx.window["compiles"])
