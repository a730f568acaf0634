"""Where XLA's persistent compilation cache lives.

A fresh machine compiles every program from cold (minutes for ResNet-50 or
the sharded transformer step), and nothing but this cache survives from one
process to the next. The directory is part of the cache key's lookup, so it
must not move between runs: it is either where the operator put it
(``JAX_COMPILATION_CACHE_DIR``, which jax reads itself) or one fixed
directory next to the package — never a temp name, a pid or a timestamp.

Every entry point that compiles calls :func:`ensure_compile_cache` before
its first compile: ``hvd.init()``, ``ParamsLifecycle`` (both serving
engines), ``bench.py``'s worker and ``chip_smoke.py``.
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure the persistent compilation cache has a directory; returns
    it. With ``JAX_COMPILATION_CACHE_DIR`` set, or a directory already
    chosen through ``jax.config``, nothing is set here."""
    import jax

    current = os.environ.get(ENV_VAR) or jax.config.jax_compilation_cache_dir
    if current:
        return current
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
