"""JAX's persistent compilation cache, at one fixed place.

A later run finds what an earlier one compiled only if both look in the
same directory, so the directory is fixed, never made from a temporary
name, a process id or the time.  ``JAX_COMPILATION_CACHE_DIR`` wins when
it is set (JAX reads it itself and this module sets nothing); otherwise
the cache lives in ``<repo root>/.jax_cache``.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
