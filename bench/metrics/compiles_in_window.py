"""Backend compilations inside the window, counted by JAX's monitoring
hooks (``/jax/core/compile/backend_compile_duration`` events). Warm-up
runs every shape the window uses, so this should read 0."""


def read(ctx):
    return ctx["compiles"]
