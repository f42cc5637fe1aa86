"""Where compiled programs are kept between processes.

One rule, called by everything that compiles for the chip (the ICI van's
data plane, ``chip_smoke.py``, the training examples): if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no directory
is set in code; otherwise the cache lives in one fixed, git-ignored
directory of the checkout.  The directory is part of what a cache entry is
found by, so it is never a temporary name, a pid or a time.

It also counts the cache's hits and misses (``jax.monitoring`` events), for
whoever wants them: ``chip_smoke.py`` prints them, the ICI van exports them
as the gauges ``compile_cache.hits`` / ``compile_cache.misses``.

The same directory keeps traced kernels (:func:`call_traced`).  JAX finds a
compiled program by its lowered module, so every process traces before it
can hit the cache; for a program with a Pallas kernel that is a second of
importing Pallas and half a second of tracing the kernel, each run.
"""

from __future__ import annotations

import hashlib
import os
import tempfile

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


# Traced kernels of this process, by the file that keeps them.
_traced: dict = {}


def _trace_dir():
    """Where traced kernels are kept: the persistent cache's directory, or
    None where that cache is off (the unit tests) or has no directory."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir or None


def call_traced(fn, source: str, platform: str, *args, static=()):
    """``fn(*args)`` inside a program that is lowered for ``platform``,
    with ``fn``'s trace kept between processes as a compiled program is.

    ``fn`` is traced and lowered alone, once (``jax.export``), and the
    result is written beside the compiled programs; a later process reads
    it back and calls it without running ``fn``, so without importing what
    ``fn`` imports.  An entry is found by the jax version, ``platform``,
    the arguments' shapes and dtypes, the bytes of the file ``source``
    (``fn``'s module: an edited kernel is traced again) and ``static``:
    whatever else ``fn`` is made from that its arguments do not show (a
    closure's numbers, as a tuple of values whose ``repr`` says them
    whole).  Where no directory keeps programs, ``fn`` is called in place.
    ``fn`` takes arrays only and runs on one device (a ``shard_map``
    body's view)."""
    import jax

    directory = _trace_dir()
    if directory is None:
        return fn(*args)
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    with open(source, "rb") as fh:
        key = hashlib.sha256(fh.read())
    key.update(repr((jax.__version__, platform,
                     [(a.shape, str(a.dtype)) for a in avals],
                     *([static] if static else []))).encode())
    path = os.path.join(directory, "traced-" + key.hexdigest())
    exported = _traced.get(path)
    if exported is None:
        try:
            with open(path, "rb") as fh:
                exported = jax.export.deserialize(fh.read())
        except Exception:  # noqa: BLE001 - absent, cut short or of another jax
            exported = jax.export.export(
                jax.jit(fn), platforms=[platform])(*avals)
            _keep(path, exported.serialize())
        _traced[path] = exported
    return exported.call(*args)


def _keep(path: str, blob: bytes) -> None:
    """Write whole or not at all; a directory that cannot be written costs
    the next process its trace, nothing else."""
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path))
        with os.fdopen(fd, "wb") as fh:
            fh.write(blob)
        os.replace(tmp, path)
    except OSError:
        pass
