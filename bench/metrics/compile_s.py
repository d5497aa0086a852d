"""Compile: seconds JAX spent tracing, lowering and compiling (or
fetching from its persistent cache) during set-up, from its
``jax.monitoring`` compile events."""


def read(win):
    return win.setup.get("compile_s")
