"""Where compiled programs persist — the ONE rule for the whole repo.

A cold ``prewarm()`` compiles two step variants plus four burst tiers
per driver; on the chip that is most of a short run's wall time, and
the persistent cache's key includes the directory path, so a directory
that moves (``tempfile``, a pid, the time) never hits. Every entry
point that compiles (``chip_smoke.py``, the benchmark drivers,
``launch_node.py``) calls :func:`use_compile_cache` before its first
jit instead of picking its own path.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — git-ignored, inside the checkout so a copied
# tree carries (or deliberately drops) its own cache
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Select the persistent compilation cache directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is
    set in code. Unset: ``<checkout>/.jax_cache``, the same path in
    every call and every process, admitting every program (a served
    run compiles dozens of sub-second helpers whose sum is not small).
    Call before the first compilation (JAX latches the directory when
    the cache first initializes)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CHECKOUT_CACHE
