"""Compile-only, beside ``test_compile_fullsize.py``: the push program of
the cell ``dlrm-criteo-rowadagrad.zipf`` (``parallel/sparse.py``
``_adagrad_sparse``: sort, segment sum, accumulator gather / update /
scatter, store scatter) at full size for the v5e, with what it would hold on
the device.  A compile that passes says the program LOWERS and FITS and
that both donations hold, never that it runs or how fast.  The topology is
described inside a fixture: only one process at a time may load the TPU's
library.
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


def test_row_adagrad_push_compiles_in_place_over_table_and_accumulator(mesh):
    """Both donated operands are aliased (no second table, no second
    accumulator), the temporaries are of the batch's size, and the program
    with the table, the accumulator and its inputs fits the chip."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel import sparse

    config = _json("configs", "dlrm-criteo-rowadagrad.json")
    lookups = _json("traffic", "zipf-rows-handle.json")["lookups_per_worker"]
    rows, dim = config["rows"], config["dim"]
    assert config["server_handle"].startswith("row_adagrad:")
    table_bytes, acc_bytes = rows * dim * 4, rows * 4
    assert (table_bytes, acc_bytes) == (10_240_000_000, 80_000_000)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    store = sds((rows, dim), jnp.float32, P("kv", None))
    acc = sds((rows,), jnp.float32, P("kv"))
    idx = sds((1, lookups), jnp.int32, P("kv", None))
    grads = sds((1, lookups, dim), jnp.float32, P("kv", None, None))
    scalar = sds((), jnp.float32, P())

    def body(st, ac, ix, g, lr, eps):
        new, acc_new = sparse._adagrad_sparse("kv", 1, rows, 1, dim, st, ac,
                                              ix, g, lr, eps)
        return new, acc_new, new[:1, :1]       # the engine's own outputs

    push = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("kv", None), P("kv"), P("kv", None), P("kv", None, None),
                  P(), P()),
        out_specs=(P("kv", None), P("kv"), P("kv", None)), check_vma=False),
        donate_argnums=(0, 1))
    compiled = push.lower(store, acc, idx, grads, scalar, scalar).compile()
    mem = compiled.memory_analysis()
    # Table and accumulator both aliased (the accumulator's tiling rounds
    # 80,000,000 B up by a few KiB), and nothing of their size beside them.
    assert table_bytes + acc_bytes <= mem.alias_size_in_bytes \
        < table_bytes + acc_bytes + (1 << 20)
    assert mem.temp_size_in_bytes < 10**9
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 0.75 * HBM, peak
    # The two whole-state results are the scatters themselves: no copy of
    # a donated operand stands among the program's operations.
    text = compiled.as_text()
    whole = [l for l in text.splitlines()
             if (f"= f32[{rows},{dim}]" in l or f"= f32[{rows}]" in l)
             and " parameter(" not in l]
    assert whole and not [l for l in whole if " copy(" in l], whole
    assert any("ps.sparse.push.scatter_add" in l for l in whole)
    assert "ps.sparse.combine" in text
