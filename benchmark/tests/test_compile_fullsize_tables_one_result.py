"""Compile-only, beside ``test_compile_fullsize_tables.py``: the engine's OWN
grouped pull (``SparseEngine._sparse_group_program("pull", ...)``) of the cell
``dlrm-terabyte-26tables.zipf`` at full size for a described v5e, as it is
since PR 53: the 26 tables' rows leave the program as ONE array,
``f32[1, 26 x 2048, 64]``, the sibling cell ``dlrm-terabyte-emb64.zipf``'s own
result shape, where they left as 26 (a result is a buffer the runtime
allocates at every launch: 47 us each on the chip's host, ``PERF.md`` §5).

Held here: one result and nothing aliased; the output within what 53,248 rows
of 64 to 128 lanes take; the temporaries of the batch's size (each table's
rows written into the one buffer in place, the buffer re-laid out once); the
whole fits the chip beside the 13.84 GB of tables; no table is read by
anything but its own gather (whole, or moved into the compiler's alternate
memory first: the small tables and one of 103 MB, as the 26-result program
staged them); every table's scope is there.  (``test_compile_fullsize_tables
.py``'s pull test still describes the 26 results and fails on this tree;
everything else it checks is checked here.)

A compile that passes says what the program IS and that it FITS, never that it
runs or how fast.  The topology is described inside a fixture: only one process
at a time may load the TPU's library.
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
def pull(mesh):
    """The cell's grouped pull, lowered: its tables registered by shape alone
    (nothing can be placed on a described chip)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.sparse import SparseEngine, SparseTable

    config = _json("configs", "dlrm-terabyte-26tables.json")
    traffic = _json("traffic", "zipf-tables-2048x26.json")
    n, dim = traffic["lookups_per_table"], config["dim"]
    names = [name for name, _ in config["tables"]]
    rows = [r for _, r in config["tables"]]
    assert (len(names), n, dim) == (26, 2048, 64)
    assert n * len(names) == traffic["lookups_per_worker"] == 53_248

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
    assert not eng._group_routed((n,) * len(names))      # one chip
    prog = eng._sparse_group_program("pull", tables, (n,) * len(names))
    return prog.lower(*stores, *idx), names, stores, n, dim


def test_the_26_tables_rows_leave_as_one_array(pull):
    lowered, names, _, n, dim = pull
    k = len(names)
    # The array bare, as the one-table pull's: no tuple of one.
    info = lowered.out_info
    assert tuple(info.shape) == (1, k * n, dim) == (1, 53_248, 64)
    assert info.dtype == np.float32


def test_the_one_result_fits_and_no_table_is_touched_but_by_its_gather(pull):
    lowered, names, stores, n, dim = pull
    k = len(names)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    # 53,248 rows of 64 f32, tiled to 128 lanes at most (13,631,488 B with
    # this compiler: the batch along the lanes, as the sibling's result).
    assert k * n * dim * 4 <= mem.output_size_in_bytes <= k * n * 128 * 4
    # Twice the batch and a little (30,166,016 B): the buffer the rows are
    # written into, table after table in place, and its re-laying.
    assert mem.temp_size_in_bytes < 10**8, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM
    text = compiled.as_text()
    entry = [l.strip() for l in text[text.index("\nENTRY "):].splitlines()]
    # One result: the entry's root is the array, re-laid out once.
    root = [l for l in entry if l.startswith("ROOT ")]
    assert len(root) == 1 and f"f32[1,{k * n},{dim}]" in root[0], root
    assert " copy(" in root[0] or " bitcast(" in root[0], root[0][:200]
    # Who reads a table: its parameter is an operand of its own gather, or of
    # a move into the alternate memory (``S(1)``) whose result that gather
    # reads.  Nothing else has a table for operand or for result.
    param = {}
    for line in entry:
        found = re.match(r"%(\S+) = f32\[(\d+),128\]\S* parameter\((\d+)\)",
                         line)
        if found and int(found.group(3)) < k:
            param[found.group(1)] = int(found.group(3))
    assert len(param) == k
    phys = {s.shape[0] for s in stores}
    readers = [l for l in entry
               if any(re.search(rf"%{re.escape(p)}\b", l.split(" = ", 1)[-1])
                      for p in param) and " parameter(" not in l]
    gathers = [l for l in readers if "ps.sparse.pull.gather/gather" in l]
    moves = [l for l in readers if l not in gathers]
    assert gathers and len(gathers) + len(moves) >= k
    assert all(re.search(r" (copy-start|slice-start)\(", l) and "S(1)" in l
               for l in moves), [l[:160] for l in moves]
    # No result of a whole table's shape in HBM: a table-shaped result is one
    # end of such a move.
    for line in entry:
        found = re.search(r"= \(?f32\[(\d+),128\]\{[^}]*\}", line)
        if found and int(found.group(1)) in phys - {n} \
                and " parameter(" not in line:
            assert "S(1)" in found.group(0), line[:200]
    # Every table's physical rows are gathered once, f32[n, 128], inside its
    # own scope under the group's.
    for name in names:
        scope = (f"ps.sparse.group/ps.sparse.table.{name}"
                 f"/ps.sparse.pull.gather/gather\"")
        assert sum(scope in l and f"= f32[{n},128]" in l
                   for l in entry if " fusion(" in l) == 1, name
    # The rows are put side by side in the group's scope, in place: one
    # update of the one buffer a table, and one re-laying of the whole where
    # the 26-result program re-laid 26 (53 copies where 78).
    assert sum(" copy(" in l for l in entry) <= 2 * k + 1
    assert sum("dynamic-update-slice" in l.split(" = ")[0]
               for l in entry) == k
