"""Where run-time caches live.

Every entry point calls :func:`enable_compile_cache` from its ``main``
(never at import).  JAX's persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says when that is set — JAX reads the
variable itself, so no other directory is set in code — and otherwise to
one fixed directory inside the checkout, ``<checkout>/.cache/jax``.  The
directory is part of what makes an entry findable again, so it is never
built from a temp name, a pid or the time.  The kernel autotuner keeps its
measurements under the same root (``kernels.autotune.cache_path``).
"""
from __future__ import annotations

import os
import pathlib

CACHE_ROOT = pathlib.Path(__file__).resolve().parents[2] / ".cache"


def compile_cache_dir() -> pathlib.Path:
    """The persistent compilation cache's directory (see module doc)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return pathlib.Path(env) if env else CACHE_ROOT / "jax"


def enable_compile_cache() -> pathlib.Path:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`;
    returns the directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
