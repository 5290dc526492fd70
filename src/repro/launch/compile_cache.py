"""JAX persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, the benchmarks) call
:func:`use_compile_cache` before their first compile; library code never
does.  The cache directory is part of each entry's key, so it must not
move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when that is set
(jax reads the variable itself) and otherwise ``<root>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root: str | Path) -> str:
    """Point jax's persistent compilation cache at a fixed directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<root>/.jax_cache``.
    """
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
