"""Where compiled programs are kept between processes.

One rule, called by everything that compiles for the chip (the ICI van's
data plane, ``chip_smoke.py``, ``bench.py``, the training examples): if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no directory
is set in code; otherwise the cache lives in one fixed, git-ignored
directory of the checkout.  The directory is part of what a cache entry is
found by, so it is never a temporary name, a pid or a time.

It also counts the cache's hits and misses (``jax.monitoring`` events), for
whoever wants them: ``chip_smoke.py`` prints them, the ICI van exports them
as the gauges ``compile_cache.hits`` / ``compile_cache.misses``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# Persistent-cache reads of this process: [hits, misses], counted from the
# first enable_compile_cache() on.
cache_counts = [0, 0]
_listening = False


def _count(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        cache_counts[0] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        cache_counts[1] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation of the process.  Every program is
    kept whatever it cost to compile: a bucketed gradient trace is dozens
    of sub-second compilations, which JAX's default one-second floor
    would leave out.
    """
    import jax

    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_listener(_count)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return placed or DEFAULT_CACHE_DIR
