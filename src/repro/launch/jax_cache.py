"""JAX's persistent compilation cache, switched on by the entry points.

``launch/serve.py`` (``run``), ``launch/compile.py`` (``main``) and
``chip_smoke.py`` call :func:`enable_compile_cache` before they compile
anything; importing the library never does.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at ``<repo>/.jax_cache``
(git-ignored): a fixed path, never derived from a temporary name, a pid
or the time, so a second run on the same checkout finds what the first
one compiled.
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$ENV_VAR`` or else
    :data:`DEFAULT_DIR`, and return that path. Call before the first
    compile: JAX settles its cache at the first compilation."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def persistent_cache_disabled():
    """Compile inside without the persistent cache, then restore it.

    For AOT bundle compiles on the CPU (an executable loaded from the
    cache re-serializes without its function library on XLA:CPU and
    fails when run; ``runtime/aot.py``), and for compiles for a
    described TPU, whose entries cannot be read back without one. JAX
    settles the cache once per process, so both edges reset it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
