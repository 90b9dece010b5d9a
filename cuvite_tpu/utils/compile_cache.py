"""Shared persistent-XLA-compile-cache setup.

Every entry point that benefits from cached executables (bench.py,
chip_smoke.py, the driver artifacts in __graft_entry__.py, the serve
CLI, the tools/ scripts) enables the cache through this one helper, so
the cache location, the min-compile-time knob, and the
CUVITE_NO_COMPILE_CACHE opt-out cannot drift apart.

Where the cache lives is decided from outside first: when
JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this helper
sets no directory.  Otherwise the cache is ``<checkout>/.jax_cache`` —
a fixed path, since the path is part of what a later run must find.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compile cache unless
    CUVITE_NO_COMPILE_CACHE is set.  Call before the first compilation;
    safe to call more than once."""
    if os.environ.get("CUVITE_NO_COMPILE_CACHE"):
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
