"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed by the path it lives under, so the cache has one fixed
home: ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), else ``<checkout>/.jax_cache``. Entry points (``chip_smoke.py``, the
benchmarks) call ``place()`` once before their first compilation.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def home() -> str:
    """The persistent cache's directory."""
    return os.environ.get(ENV) or str(CHECKOUT_CACHE)


def place() -> str:
    """Put the persistent cache at its home and return that directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX's own settings stand and
    nothing is written, unless a ``use()`` moved the cache elsewhere: then the
    directory and JAX's default admission floors come back."""
    import jax

    path = home()
    if not os.environ.get(ENV):
        use(path)
    elif jax.config.jax_compilation_cache_dir != path:
        use(path, persist_all=False)
    return path


def use(path: Optional[str], persist_all: bool = True) -> None:
    """Point the persistent cache at ``path``; ``None`` turns it off.

    With ``persist_all`` every program persists: the size and compile-time
    admission floors are dropped, since a fleet core is small but costs
    seconds of compilation. Otherwise JAX's default floors hold. JAX decides
    whether a cache is in use at the process's first compilation, so the
    decision is reset here."""
    import jax
    from jax.experimental.compilation_cache.compilation_cache import reset_cache

    keep_all = path is not None and persist_all
    jax.config.update("jax_compilation_cache_dir",
                      str(path) if path is not None else None)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      -1 if keep_all else 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.0 if keep_all else 1.0)
    reset_cache()
