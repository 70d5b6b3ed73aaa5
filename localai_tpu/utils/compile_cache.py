"""Where the persistent XLA compilation cache lives.

One rule for every start-up path (`ModelManager`, hence the CLI, and
`chip_smoke.py`): if `JAX_COMPILATION_CACHE_DIR` is set the operator placed
the cache and jax reads the variable itself, so nothing is set in code;
otherwise the cache sits at one fixed directory inside the checkout. The
directory is part of the cache key's world — a path built from the home
directory, a temp dir, a pid or the time never hits on the next start.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (listed in .gitignore). Derived from this file's own
# location so it is the same from any working directory.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at the directory the rule
    above names and return it. A directory that cannot be created raises:
    a server that silently recompiles every program on every start is a
    mis-deployment, not a degraded mode."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, not only the slow ones: a restart should find
    # all of them, and "the second start added no entries" is only a
    # checkable statement when no entry depends on a compile-time threshold.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
