"""Seconds JAX spent obtaining executables before the window opened
(``/jax/core/compile/backend_compile_duration``: a real compile on a
persistent-cache miss, a read on a hit)."""

def read(ctx):
    return ctx["compile"]["setup"]["seconds"]
