"""Compile-only, beside ``test_compile_fullsize_packed.py``: the engine's OWN
two group programs (``SparseEngine._sparse_group_program``, not their bodies
alone) of the cell ``dlrm-terabyte-26tables.zipf`` at full size for a described
v5e: 26 tables of 3 to 10,000,000 rows, 64 f32 lanes kept two to a 128-lane
physical row, 2,048 lookups a table, the push under the plain sum and the
pull.

Held here: every one of the 26 stores is donated and ALIASED (no second copy
of any table: a copy of one 2.56 GB table would not fit beside the rest), the
temporaries are of the batches' size, the whole fits the chip, and nothing in
the push has a result of a table's shape but that table's ``row_add``: tables
of two physical rows and of five million go through the same kernel.  A
compile that passes says the programs LOWER and FIT, never that they run or
how fast.  The topology is described inside a fixture: only one process at a
time may load the TPU's library.
"""

import json
import os
import re

import numpy as np
import pytest

from conftest import BENCH

jax = pytest.importorskip("jax")

HBM = 16 * 10**9


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    from jax.sharding import Mesh

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # environment, not code
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices[:1]), ("kv",))


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cell(mesh):
    """The engine with the cell's tables registered by shape alone (nothing
    can be placed on a described chip), and the programs' arguments."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.sparse import SparseEngine, SparseTable

    config = _json("configs", "dlrm-terabyte-26tables.json")
    traffic = _json("traffic", "zipf-tables-2048x26.json")
    n, dim = traffic["lookups_per_table"], config["dim"]
    names = [name for name, _ in config["tables"]]
    rows = [r for _, r in config["tables"]]
    assert (len(names), n, dim) == (26, 2048, 64)
    assert sum(rows) == config["rows"] == 54_063_992
    assert n * len(names) == traffic["lookups_per_worker"] == 53_248
    assert config["server_handle"] == "sum" and config["reduced"] == []
    assert (min(rows), max(rows)) == (3, 10_000_000)
    assert sum(r <= n for r in rows) == 10     # more slots than rows

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    eng = SparseEngine(mesh)
    pack = 128 // dim
    tables = []
    for name, r in zip(names, rows):
        rps = -(-r // pack) * pack
        table = SparseTable(name, r, dim, rps, jnp.float32, pack=pack)
        eng._tables[name] = table
        eng._stores[name] = sds((table.phys_rows, pack * dim), jnp.float32,
                                "kv", None)
        tables.append(table)
    stores = [eng._stores[name] for name in names]
    idx = [sds((1, n), jnp.int32, "kv", None)] * len(names)
    grads = [sds((1, n, dim), jnp.float32, "kv", None, None)] * len(names)
    assert not eng._group_routed((n,) * len(names))      # one chip
    return eng, tables, stores, idx, grads, n, dim


def test_the_grouped_push_writes_26_tables_in_place(cell):
    eng, tables, stores, idx, grads, n, dim = cell
    k = len(tables)
    prog = eng._sparse_group_program("push", tables, (n,) * k)
    compiled = prog.lower(*stores, *idx, *grads).compile()
    mem = compiled.memory_analysis()
    # Every store aliased: 13.84 GB, and no second copy of any table.
    logical = sum(s.shape[0] * s.shape[1] * 4 for s in stores)
    assert logical == 27_032_000 * 512 == 13_840_384_000
    # (As the chip lays them out: a table's rows are tiled by 8 at most,
    # 13,840,410,624 B with this compiler.)
    assert logical <= mem.alias_size_in_bytes < logical + k * 8 * 512, (
        mem.alias_size_in_bytes)
    assert mem.temp_size_in_bytes < 10**8, mem.temp_size_in_bytes
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 0.25 * HBM < peak < HBM, peak
    text = compiled.as_text()
    # A table of a million rows or more is touched by its kernel alone: the
    # one result of its shape is its ``row_add``'s; no scatter into it, no
    # copy of the donated operand.  The 18 smaller tables (39,043 rows, 10
    # MB, and under) this compiler stages whole through its alternate memory
    # and back (``S(1)``: slices or a copy in, ``row_add`` there, a copy out
    # into the aliased buffer): every other result of a table's shape is one
    # end of such a move, never a second table in HBM.
    def shape_of(line):
        found = re.search(r"= \(?(f32\[\d+,128\])", line)
        return found.group(1) if found else None

    shapes = {f"f32[{s.shape[0]},{s.shape[1]}]": s.shape[0] for s in stores}
    whole = [l.strip() for l in text[text.index("\nENTRY "):].splitlines()
             if shape_of(l) in shapes
             and not re.search(r" (parameter|get-tuple-element|bitcast|"
                               r"tuple|copy-done|slice-done)\(", l)]
    kernels = [l for l in whole if l.startswith("%row_add")]
    assert len(kernels) == k
    for line in whole:
        if not line.startswith("%row_add"):
            assert shapes[shape_of(line)] < 500_000 and "S(1)" in line, \
                line[:200]
    assert text.count('custom_call_target="tpu_custom_call"') >= 2 * k
    for scope in ("ps.sparse.group", "ps.sparse.table.emb00",
                  "ps.sparse.table.emb25", "ps.sparse.pack.place",
                  "ps.sparse.combine", "ps.sparse.push.scatter_add"):
        assert scope in text, scope


def test_the_grouped_pull_reads_26_tables_and_keeps_none(cell):
    eng, tables, stores, idx, _, n, dim = cell
    k = len(tables)
    prog = eng._sparse_group_program("pull", tables, (n,) * k)
    lowered = prog.lower(*stores, *idx)
    outs = jax.tree_util.tree_leaves(lowered.out_info)
    assert [tuple(o.shape) for o in outs] == [(1, n, dim)] * k
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    # f32[2048,64] a table, tiled to 128 lanes at most.
    assert k * n * dim * 4 <= mem.output_size_in_bytes <= k * n * 128 * 4
    assert mem.temp_size_in_bytes < 10**8
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM
    text = compiled.as_text()
    assert "ps.sparse.group" in text and "ps.sparse.pull.gather" in text
    assert "ps.sparse.table.emb05" in text
