"""Where the entry points keep JAX's persistent compilation cache.

JAX itself reads ``JAX_COMPILATION_CACHE_DIR``; when that is set it is
used as is.  Otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``), so every run
from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
