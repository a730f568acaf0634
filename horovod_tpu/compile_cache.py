"""Where XLA's persistent compilation cache lives.

A fresh machine compiles every program from cold (minutes for ResNet-50 or
the sharded transformer step), and nothing but this cache survives from one
process to the next. The directory is part of the cache key's lookup, so it
must not move between runs: it is either where the operator put it
(``JAX_COMPILATION_CACHE_DIR``, which jax reads itself) or one fixed
directory next to the package — never a temp name, a pid or a timestamp.

Every entry point that compiles calls :func:`ensure_compile_cache` before
its first compile: ``hvd.init()``, ``ParamsLifecycle`` (both serving
engines) and ``chip_smoke.py``. The same call
starts the program's own count of what jax builds
(``hvd_tpu_compile_total``, ``hvd_tpu_compile_seconds_total``,
``hvd_tpu_compile_cache_misses_total``), so an operator sees a recompile
in a serving process and whether a start-up built its programs or
loaded them.
"""

import os
import threading

from . import metrics as _metrics

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


#: jax.monitoring's names on jax 0.9.0: the duration event wraps
#: ``compiler.compile_or_get_cached`` (``pxla.py``), so it fires once a
#: program whether XLA compiled it or the persistent cache returned it;
#: the miss event fires when a compiled program is written to the cache
#: (``compilation_cache.put_executable_and_time``)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_M_COMPILES = _metrics.counter(
    "hvd_tpu_compile_total",
    "Programs jax built in this process, compiled by XLA or read back "
    "from the persistent compilation cache (jax's "
    "backend_compile_duration event). A count that moves while a "
    "server is taking traffic is a recompile: a shape that start-up "
    "did not warm.")
_M_COMPILE_SECONDS = _metrics.counter(
    "hvd_tpu_compile_seconds_total",
    "Seconds spent building those programs: XLA compilation on a cold "
    "cache, reads from the cache on a warm one. Start-up's share of "
    "time to readiness.")
_M_CACHE_MISSES = _metrics.counter(
    "hvd_tpu_compile_cache_misses_total",
    "Programs that were compiled and then written to the persistent "
    "compilation cache: the cache did not hold them (jax's "
    "cache_misses event; programs under jax's size and compile-time "
    "thresholds are not cached and not counted). 0 after a warm start.")

_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def _on_duration(event, duration, **kwargs):
    if event == COMPILE_EVENT:
        _M_COMPILES.inc()
        _M_COMPILE_SECONDS.inc(duration)


def _on_event(event, **kwargs):
    if event == CACHE_MISS_EVENT:
        _M_CACHE_MISSES.inc()


def _listen(jax) -> None:
    global _LISTENING
    with _LISTEN_LOCK:
        if not _LISTENING:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _LISTENING = True


def ensure_compile_cache() -> str:
    """Make sure the persistent compilation cache has a directory; returns
    it. With ``JAX_COMPILATION_CACHE_DIR`` set, or a directory already
    chosen through ``jax.config``, nothing is set here. The first call
    also registers the compile counters' listeners."""
    import jax

    _listen(jax)
    current = os.environ.get(ENV_VAR) or jax.config.jax_compilation_cache_dir
    if current:
        return current
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
