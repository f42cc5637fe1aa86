"""Compile-only, beside ``test_compile_fullsize_sparse_4chip.py``: the
engine's OWN pull program (``SparseEngine._sparse_program("pull", ...)``, not
its body alone) at the full size of the sparse cells for a described v5e:
``dlrm-criteo-emb.zipf`` and ``dlrm-criteo-rowadagrad.zipf`` (20,000,000 rows
of 128 f32 lanes, 131,072 lookups, one chip), ``dlrm-criteo-emb.zipf.4chip``
(80,000,000 rows over four chips, routed by owner) and
``dlrm-terabyte-emb64.zipf`` (54,000,000 rows of 64 lanes kept two to a
physical row, 53,248 lookups).

Since PR 49 the program hands its rows back as ``[W, n, d]``, a worker's batch
a chip, and ``SparseEngine.pull`` returns that array: the eager reshape that
followed, a program of its own (``jit_reshape``) that wrote the batch once
more, is gone.  Held here: the leading unit dimension costs nothing.  After
the gather (routed: after the rows' ``all-to-all`` and the permutation back to
the batch's order) the compiled program writes nothing of the batch's size
again, on one chip and on four.  The 64-wide result is the exception that was
there before: the compiler lays it with the batch along the lanes and ends the
program in one copy that re-lays it, as the parent's program ended.

A compile that passes says what the program IS, never how fast it runs.  The
topology is described inside a fixture: only one process at a time may load
the TPU's library.
"""

import json
import os
import re

import numpy as np
import pytest

from conftest import BENCH

jax = pytest.importorskip("jax")

CELLS = {
    # configuration, traffic, chips
    "dlrm-criteo-emb.zipf": ("dlrm-criteo-emb", "zipf-rows", 1),
    "dlrm-criteo-emb.zipf.4chip": ("dlrm-criteo-emb-4chip", "zipf-rows", 4),
    "dlrm-terabyte-emb64.zipf": ("dlrm-terabyte-emb64", "zipf-rows-2048x26",
                                 1),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # environment, not code
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def _compiled_pull(topo, config, traffic, chips):
    """The engine's pull program of one cell, its table registered by shape
    alone (nothing can be placed on a described chip)."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.sparse import SparseEngine, SparseTable

    config = _json("configs", config + ".json")
    n = _json("traffic", traffic + ".json")["lookups_per_worker"]
    rows, dim = config["rows"], config["dim"]
    assert config["chips"] == chips and rows % chips == 0
    mesh = Mesh(np.array(topo.devices[:chips]), ("kv",))

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    eng = SparseEngine(mesh)
    pack = 128 // dim
    table = SparseTable("emb", rows, dim, rows // chips, jnp.float32,
                        pack=pack)
    eng._tables["emb"] = table
    eng._stores["emb"] = sds((table.phys_rows * chips, pack * dim),
                             jnp.float32, "kv", None)
    count = [sds((chips,), jnp.int32, "kv")] * eng._routed(n)
    assert eng._routed(n) == (chips > 1)
    lowered = eng._sparse_program("pull", table, n).lower(
        eng._stores["emb"], sds((chips, n), jnp.int32, "kv", None), *count)
    rows_out = jax.tree_util.tree_leaves(lowered.out_info)[0]
    assert tuple(rows_out.shape) == (chips, n, dim)
    return lowered.compile(), n, dim


def _moves_of_the_batch(text, n, dim):
    """Instructions that write a worker's batch over again: a copy, or a
    transpose that is none of the gather's own (``dimensions={0,1}``, the
    identity, inside its fusion), with the batch for result."""
    batch = re.compile(
        rf"= f32\[(1,)?{n},{dim}\]\S* (copy|copy-start|transpose)\(")
    return [l.strip() for l in text.splitlines() if batch.search(l)
            and "dimensions={0,1}" not in l]


def _entry_root(text):
    return [l.strip() for l in text[text.index("\nENTRY "):].splitlines()
            if l.strip().startswith("ROOT ")][0]


@pytest.mark.parametrize("cell", ["dlrm-criteo-emb.zipf",
                                  "dlrm-criteo-emb.zipf.4chip"])
def test_a_128_wide_pull_writes_the_batch_once(topo, cell):
    compiled, n, dim = _compiled_pull(topo, *CELLS[cell])
    text = compiled.as_text()
    assert not _moves_of_the_batch(text, n, dim)
    root = _entry_root(text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes <= 4096        # the donated count alone
    if CELLS[cell][2] == 1:
        # The gather's fusion writes the result; the unit dimension over it
        # is a bitcast, and the program keeps nothing else.
        assert f"f32[1,{n},{dim}]" in root and " bitcast(" in root, root
        assert mem.temp_size_in_bytes == 0
        assert mem.output_size_in_bytes == n * dim * 4 == 67_108_864
    else:
        # Rows and count; the rows are the one conditional's result, and
        # each of its branches ends in a bitcast of what it gathered.
        assert " tuple(" in root and "%conditional" in root, root
        cond = [l for l in text.splitlines() if " conditional(" in l]
        assert len(cond) == 1 and f"= f32[1,{n},{dim}]" in cond[0], cond
        ends = [l.strip() for l in text.splitlines()
                if l.strip().startswith("ROOT ")
                and f"f32[1,{n},{dim}]" in l.split(" = ")[1].split("(")[0]]
        assert ends and all(
            re.search(r" (bitcast|fusion)\(", l) for l in ends), ends
        assert n * dim * 4 <= mem.output_size_in_bytes <= n * dim * 4 + 4096


def test_the_64_wide_pull_ends_in_the_one_relaying_it_always_had(topo):
    compiled, n, dim = _compiled_pull(topo, *CELLS["dlrm-terabyte-emb64.zipf"])
    text = compiled.as_text()
    moves = _moves_of_the_batch(text, n, dim)
    # The compiler's layout of a 64-wide result: the batch along the lanes,
    # no lane padded (13.6 MB, not 27.3).  The select that picks each
    # row's half writes it row-major, and the program's last instruction
    # re-lays it: the parent's ``f32[53248,64]{0,1}`` result was the same
    # bytes in the same places, written by the same copy.
    assert len(moves) == 1 and moves[0].startswith("ROOT "), moves
    assert f"f32[1,{n},{dim}]{{1,2,0:T(8,128)}} copy(" in moves[0], moves[0]
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == n * dim * 4 == 13_631_488
    assert mem.alias_size_in_bytes == 0
