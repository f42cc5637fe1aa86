"""Compile-only, beside ``test_compile_fullsize_tables.py``: the engine's OWN
two group programs (``SparseEngine._sparse_group_program``) of the cell
``dlrm-dcnv2-multihot.bags`` at full size for a described v5e: 26 tables of 3
to 4,000,000 rows, 128 f32 lanes, 4,096 BAGS a table of 1 to 100 ids each
(876,544 lookups a step), the pooled pull and the pooled push under
``row_adagrad``.

Held here: both programs LOWER at the cell's shapes (one table's body over
409,600 slots; both sides of ``_acc_update_takes`` in one program: the five
capped tables take ``ops/acc_update.py``'s pass, the others keep XLA's pair);
every store and accumulator of the push is donated and aliased; the pooled
pull's one result is ``f32[1, 106496, 128]`` and no ``[B * h, d]`` gradient is
a parameter of the push; and each program's peak leaves 1 GB under the
device's ``bytes_limit``: the number that settles the configuration's cap
(``max_ind_range``).  A compile that passes says the programs lower and fit,
never that they run or how fast.  The topology is described inside a fixture:
only one process at a time may load the TPU's library.
"""

import json
import os

import numpy as np
import pytest

from conftest import BENCH

jax = pytest.importorskip("jax")

# What ``jax.devices()[0].memory_stats()["bytes_limit"]`` reads on a v5e.
BYTES_LIMIT = 16_909_000_000
ROOM = 10**9


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

    config = _json("configs", "dlrm-dcnv2-multihot.json")
    traffic = _json("traffic", "zipf-bags-4096x214.json")
    B, dim = traffic["bags_per_table"], config["dim"]
    names = [name for name, _ in config["tables"]]
    rows = [r for _, r in config["tables"]]
    hs = config["bag_sizes"]
    cap = config["sizes"]["max_ind_range"]
    assert (len(names), len(hs), B, dim) == (26, 26, 4096, 128)
    assert hs == config["sizes"]["multi_hot_sizes"] and sum(hs) == 214
    assert rows == [min(c, cap) for c in
                    config["sizes"]["num_embeddings_per_feature"]]
    assert sum(rows) == config["rows"]
    assert B * sum(hs) == traffic["lookups_per_worker"] == 876_544
    assert config["reduced"] == ["max_ind_range"]
    # The cap is 4,000,000 less whole steps of 256,000, not under 3,232,000: a
    # multiple of 128 above emb10's rows, so that exactly the five capped
    # tables change and ``_acc_update_takes`` keeps its verdict.
    assert (4_000_000 - cap) % 256_000 == 0 and 3_232_000 <= cap <= 4_000_000
    assert [h for r, h in zip(rows, hs) if r == cap] == [3, 7, 12, 100, 27]
    assert sum(h == 1 for h in hs) == 11

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    eng = SparseEngine(mesh)
    tables = []
    for name, r in zip(names, rows):
        table = SparseTable(name, r, dim, r, jnp.float32, pack=1)
        eng._tables[name] = table
        eng._stores[name] = sds((r, dim), jnp.float32, "kv", None)
        tables.append(table)
    stores = [eng._stores[name] for name in names]
    accs = [sds((r,), jnp.float32, "kv") for r in rows]
    # Bags of one id lie on the device as [1, B, 1] and go to the program as
    # they lie.
    idx = [sds((1, B, h), jnp.int32, "kv", None, None) for h in hs]
    grads = [sds((1, B, dim), jnp.float32, "kv", None, None)] * len(names)
    batches = tuple(B if h == 1 else (B, h) for h in hs)
    assert not eng._group_routed(batches)                # one chip
    return eng, tables, stores, accs, idx, grads, batches, config


def _peak(mem):
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def test_the_pooled_pull_lowers_and_gives_one_row_a_bag(cell):
    eng, tables, stores, _, idx, _, batches, config = cell
    prog = eng._sparse_group_program("pull", tables, batches)
    lowered = prog.lower(*stores, *idx)
    outs = jax.tree_util.tree_leaves(lowered.out_info)
    assert [tuple(o.shape) for o in outs] == [(1, 26 * 4096, 128)]
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert mem.output_size_in_bytes == 106_496 * 512
    logical = config["rows"] * 512
    assert logical <= mem.argument_size_in_bytes < logical + 10**8
    peak = _peak(mem)
    print(f"pooled pull: temp {mem.temp_size_in_bytes:,} B, peak {peak:,} B")
    assert peak + ROOM < BYTES_LIMIT, peak
    text = compiled.as_text()
    for scope in ("ps.sparse.group", "ps.sparse.table.emb20",
                  "ps.sparse.pull.gather", "ps.sparse.pull.pool"):
        assert scope in text, scope


def test_the_pooled_push_lowers_in_place_and_takes_both_accumulator_rules(
        cell):
    eng, tables, stores, accs, idx, grads, batches, config = cell
    k = len(tables)
    # Both sides of the rule in one program, as the issue reckons them.
    from pslite_tpu.parallel.sparse import _acc_update_takes, _lookups

    takes = [_acc_update_takes(t.rows_per_shard, _lookups(b))
             for t, b in zip(tables, batches)]
    cap = config["sizes"]["max_ind_range"]
    assert [t.num_rows for t, took in zip(tables, takes) if took
            and t.num_rows == cap] == [cap] * 5
    assert not any(took for t, took in zip(tables, takes)
                   if t.num_rows % 128)
    scalar = jax.ShapeDtypeStruct((), np.float32)
    prog = eng._sparse_group_program("push_row_adagrad", tables, batches)
    lowered = prog.lower(*stores, *accs, *idx, *grads, scalar, scalar)
    # One gradient a BAG: no [B * h, d] array is a parameter.
    shapes = [tuple(a.shape) for a in jax.tree_util.tree_leaves(
        lowered.in_avals if hasattr(lowered, "in_avals") else lowered.args_info)]
    assert (1, 4096 * 100, 128) not in shapes
    assert shapes.count((1, 4096, 128)) == k
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    logical = config["rows"] * 516
    assert logical <= mem.alias_size_in_bytes < logical + 10**8, (
        mem.alias_size_in_bytes)
    peak = _peak(mem)
    print(f"pooled push: temp {mem.temp_size_in_bytes:,} B, peak {peak:,} B")
    assert peak + ROOM < BYTES_LIMIT, peak
    text = compiled.as_text()
    assert text.count("%acc_update") >= sum(takes)
    for scope in ("ps.sparse.table.emb20", "ps.sparse.combine",
                  "ps.sparse.push.bag", "ps.update",
                  "ps.sparse.push.scatter_add"):
        assert scope in text, scope
