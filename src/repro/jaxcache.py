"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` before their first compile; importing
``repro`` never touches the cache.  ``JAX_COMPILATION_CACHE_DIR``, when
set, names the directory and nothing else is set in code.  Otherwise the
cache lives at a fixed ``<checkout>/.jax_cache``: the path is part of the
cache key, so a directory made from a temp name, a pid or the time would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir(root: Path = CHECKOUT) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<root>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(Path(root) / ".jax_cache"))


def use_compile_cache(root: Path = CHECKOUT) -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax

    path = compile_cache_dir(root)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
