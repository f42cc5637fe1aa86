"""``utils/compile_cache.py`` ``call_traced``: a function's trace kept in
the compile cache's directory, found again by a later process, made again
when what it was made from changes.  (That the kernel it exists for comes
back without Pallas, in place, at full size: ``test_compile_for_v5e.py``.)
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from pslite_tpu.utils import compile_cache  # noqa: E402


@pytest.fixture()
def kept(tmp_path, monkeypatch):
    """A directory that keeps programs (the unit tests run with the cache
    off), and what a function would be found by in it."""
    monkeypatch.setattr(compile_cache, "_trace_dir", lambda: str(tmp_path))
    source = tmp_path / "kernel.py"
    source.write_text("# version 1\n")
    return tmp_path, str(source)


def _entries(directory):
    return sorted(f for f in os.listdir(directory) if f.startswith("traced-"))


def _forget():
    """What a new process knows: nothing."""
    compile_cache._traced.clear()


def test_a_trace_is_made_once_and_found_by_the_next_process(kept):
    directory, source = kept
    runs = []

    def fn(x, y):
        runs.append(x.shape)
        return x * 2.0 + y

    x = np.arange(8, dtype=np.float32)
    call = jax.jit(lambda a, b: compile_cache.call_traced(
        fn, source, "cpu", a, b))
    want = x * 2.0 + 1.0
    assert (np.asarray(call(x, np.ones(8, np.float32))) == want).all()
    assert runs == [(8,)] and len(_entries(directory)) == 1
    # Another program of this process, then a new process: no trace of fn.
    again = jax.jit(lambda a, b: compile_cache.call_traced(
        fn, source, "cpu", a, b) + 0.0)
    assert (np.asarray(again(x, np.ones(8, np.float32))) == want).all()
    _forget()
    third = jax.jit(lambda a, b: 1.0 * compile_cache.call_traced(
        fn, source, "cpu", a, b))
    assert (np.asarray(third(x, np.ones(8, np.float32))) == want).all()
    assert runs == [(8,)] and len(_entries(directory)) == 1


@pytest.mark.parametrize("change", ["shape", "dtype", "source", "static"])
def test_an_entry_is_found_by_what_the_trace_depends_on(kept, change):
    directory, source = kept
    runs = []

    def fn(x):
        runs.append((x.shape, str(x.dtype)))
        return x + 1

    def call(x, **kw):
        jax.jit(lambda a: compile_cache.call_traced(
            fn, source, "cpu", a, **kw)).lower(x)

    call(np.zeros(8, np.float32))
    _forget()
    if change == "static":
        # What a closure is made from and its arguments do not show.
        call(np.zeros(8, np.float32), static=("lr", 1e-3))
        call(np.zeros(8, np.float32), static=("lr", 1e-3))
    elif change == "shape":
        call(np.zeros(16, np.float32))
    elif change == "dtype":
        call(np.zeros(8, np.int32))
    else:
        with open(source, "a") as fh:
            fh.write("# version 2\n")
        call(np.zeros(8, np.float32))
    assert len(_entries(directory)) == 2
    # (jax itself remembers this process's trace of the one ``fn`` object;
    # an edited source comes with a new process.)
    assert len(runs) == (1 if change in ("source", "static") else 2)


def test_a_cut_entry_is_made_again_and_no_directory_means_in_place(
        kept, monkeypatch):
    directory, source = kept
    runs = []

    def fn(x):
        runs.append(1)
        return x - 1.0

    def call():
        return jax.jit(lambda a: compile_cache.call_traced(
            fn, source, "cpu", a))(np.ones(4, np.float32))

    call()
    (entry,) = _entries(directory)
    with open(directory / entry, "r+b") as fh:
        fh.truncate(10)
    _forget()
    assert (np.asarray(call()) == 0.0).all()
    assert os.path.getsize(directory / entry) > 10     # whole again
    # The unit tests' own state: the cache off, nothing kept, fn in place.
    monkeypatch.undo()
    assert compile_cache._trace_dir() is None
    _forget()
    before = len(runs)
    assert (np.asarray(call()) == 0.0).all() and len(runs) == before + 1
    assert len(_entries(directory)) == 1
