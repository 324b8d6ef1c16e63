"""Placement of JAX's persistent compilation cache.

Every device program of the engine is a jitted XLA program, so a cold
process pays seconds of compile per kernel site before its first window.
JAX's persistent cache turns a restart into disk reads — but its directory
is part of what an operator places: `JAX_COMPILATION_CACHE_DIR`, when set,
is read by JAX itself and nothing here overrides it. Otherwise the cache
sits at one fixed path inside the checkout (`.jax_cache/`, git-ignored), so
a second process started from the same tree finds what the first compiled.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def setup() -> str:
    """Point JAX at the compile cache before the first compile; returns
    the directory in use. Call before anything touches a jax backend."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not placed:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # the boundary programs (finalize, reset_pane) compile in well under
    # JAX's default 1 s floor; a warm start must find them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return placed or DEFAULT_DIR
