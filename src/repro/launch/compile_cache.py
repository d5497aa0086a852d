"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it on
its own and nothing here overrides it.  Otherwise the cache lives at a
fixed path inside the checkout, ``<checkout>/.jax_cache`` (listed in
``.gitignore``).  The path never depends on a temp name, a process id
or the time, so a later run from the same checkout finds the entries.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call it before the first compile, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
