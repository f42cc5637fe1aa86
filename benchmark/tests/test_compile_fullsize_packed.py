"""Compile-only, beside ``test_compile_fullsize_handle.py``: the two
programs of the cell ``dlrm-terabyte-emb64.zipf`` at full size for the v5e,
54,000,000 rows of 64 f32 lanes kept two to a 128-lane physical row, 53,248
lookups: the ``sum`` push (``parallel/sparse.py`` ``_scatter_rows``: the rows
placed in their slot's lanes, combined by physical row, written by
``ops/row_add.py``) and the packed pull.  A compile that passes says the
programs LOWER and FIT with the table donated and aliased, never that they
run or how fast.  The topology is described inside a fixture: only one
process at a time may load the TPU's library.
"""

import json
import os

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


def test_packed_sum_push_and_pull_compile_in_place_over_the_whole_table(mesh):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel import sparse

    config = _json("configs", "dlrm-terabyte-emb64.json")
    lookups = _json("traffic", "zipf-rows-2048x26.json")["lookups_per_worker"]
    rows, dim = config["rows"], config["dim"]
    assert (rows, dim, lookups) == (54_000_000, 64, 53_248)
    assert config["server_handle"] == "sum" and config["reduced"] == []
    pack = 128 // dim
    phys, table_bytes = rows // pack, rows * dim * 4
    assert table_bytes == 13_824_000_000

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    store = sds((phys, pack * dim), jnp.float32, P("kv", None))
    idx = sds((1, lookups), jnp.int32, P("kv", None))
    grads = sds((1, lookups, dim), jnp.float32, P("kv", None, None))

    def body(st, ix, g):
        new = sparse._scatter_rows("kv", 1, rows, pack, dim, st, ix, g)
        return new, new[:1, :1]                # the engine's own outputs

    push = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("kv", None), P("kv", None), P("kv", None, None)),
        out_specs=(P("kv", None), P("kv", None)), check_vma=False),
        donate_argnums=(0,))
    compiled = push.lower(store, idx, grads).compile()
    mem = compiled.memory_analysis()
    # The table is aliased (no second table), the temporaries are of the
    # batch's size (a few f32[53248,128] workspaces of 27 MB), and the
    # program with the table and its inputs fits the chip.
    assert mem.alias_size_in_bytes == table_bytes
    assert mem.temp_size_in_bytes < 3 * 10**8, mem.temp_size_in_bytes
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < HBM, peak
    # The one result of the table's shape is the kernel's: no scatter into
    # the table, no copy of the donated operand.
    text = compiled.as_text()
    whole = [l for l in text.splitlines()
             if f"= f32[{phys},{pack * dim}]" in l
             and " parameter(" not in l]
    assert whole and all("row_add" in l for l in whole), whole
    assert "ps.sparse.pack.place" in text and "ps.sparse.combine" in text

    pull = jax.jit(jax.shard_map(
        lambda st, ix: sparse._pull_rows("kv", 1, st, ix, pack=pack,
                                         dim=dim),
        mesh=mesh, in_specs=(P("kv", None), P("kv", None)),
        out_specs=P("kv", None), check_vma=False))
    mem = pull.lower(store, idx).compile().memory_analysis()
    assert mem.alias_size_in_bytes == 0
    # f32[53248,64] is tiled to 128 lanes on the device.
    assert lookups * dim * 4 <= mem.output_size_in_bytes \
        <= lookups * 128 * 4
    assert mem.temp_size_in_bytes < 3 * 10**8
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM
