"""Where JAX's persistent compilation cache lives.

One rule, applied by the entry points (``tclb``, ``chip_smoke.py``, the
pool worker) and never at package import: a directory given from outside
through ``JAX_COMPILATION_CACHE_DIR`` is JAX's own business and nothing
else is set in code; otherwise the cache sits at a fixed path inside the
checkout.  The path is part of the cache key, so it must not move
between processes — no temp name, pid or time.
"""

from __future__ import annotations

import os

#: the fixed fallback, ``<checkout>/.jax_cache`` (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def place_compile_cache() -> str:
    """Make compiles persist across processes; returns the directory in
    use.  Call before the first compile of the process."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given          # JAX reads the variable itself
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
