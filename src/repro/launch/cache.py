"""JAX's persistent compilation cache for the program's entry points.

`chip_smoke.py`, `launch.train.main` and `launch.serve.main` call
`setup_compile_cache` before they compile anything.  The cache lives where
``JAX_COMPILATION_CACHE_DIR`` says when it is set (JAX reads the variable
itself), and otherwise at the fixed, git-ignored ``<checkout>/.jax_cache``:
the path is part of the cache key, so a directory that moved would never
hit.

The key includes each program's metadata: the ``jax.named_scope`` names
that a profiler trace shows for each operation (``rmw.sort``,
``bfs.expand``) are part of it, so a program cached from other source never
comes back under that source's names.  The metadata also holds the source
lines, so an edit that shifts them, or a checkout that moved, compiles anew.
"""

from __future__ import annotations

import os

import jax

#: the checkout root: src/repro/launch/cache.py -> four levels up
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
