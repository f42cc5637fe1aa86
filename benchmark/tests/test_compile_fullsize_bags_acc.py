"""Compile-only, beside ``test_compile_fullsize_bags.py``, whose push this is:
the engine's own grouped push of the cell ``dlrm-dcnv2-multihot.bags`` under
``row_adagrad`` at full size for a described v5e, since PR 55 with EVERY
table's accumulator updated by ``ops/acc_update.py``: an accumulator is kept
in whole 128s a shard (``parallel/sparse.py`` ``_acc_rows``), so the pass is
taken by what it costs (``_acc_update_takes``) and not by whether the table's
row count divides by 128 (of the 26, the five capped tables' and 7,424 do).

Held here: 26 ``%acc_update`` custom calls, no scatter whose result is an
accumulator, every store and every accumulator donated and aliased, the peak
1 GB under the device's ``bytes_limit``; and the push of the one other cell
that runs ``_update_acc``, ``dlrm-criteo-rowadagrad.zipf`` (20,000,000 = 128 x
156,250 rows), compiled to the text it has under the rule as it stood before
(``R % 128 == 0 and`` the same cost).

That file's ``test_the_pooled_push_lowers_in_place_and_takes_both_accumulator_
rules`` asserts the rule as it stood (no table whose rows are no multiple of
128 takes the pass) and fails on this program until a PR that may edit it
takes this file's check in its place (``PERF.md`` section 7).  A compile that
passes says the programs lower and fit, never that they run or how fast.  The
topology is described inside a fixture: only one process at a time may load
the TPU's library.
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


def _sds(mesh, shape, dtype, *spec):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P(*spec)))


def _engine(mesh, tables):
    """An engine with ``tables`` (name, rows, dim) registered by shape alone
    (nothing can be placed on a described chip); the tables, the stores and
    the accumulators as the engine keeps them."""
    import jax.numpy as jnp

    from pslite_tpu.parallel.sparse import SparseEngine, SparseTable

    eng = SparseEngine(mesh)
    made = []
    for name, rows, dim in tables:
        table = SparseTable(name, rows, dim, rows, jnp.float32, pack=1)
        eng._tables[name] = table
        eng._stores[name] = _sds(mesh, (rows, dim), jnp.float32, "kv", None)
        made.append(table)
    stores = [eng._stores[t.name] for t in made]
    accs = [_sds(mesh, (t.acc_rows,), jnp.float32, "kv") for t in made]
    return eng, made, stores, accs


def _lines(text):
    return [l.replace("ROOT ", "").strip() for l in text.splitlines()]


def test_every_accumulator_of_the_pooled_push_takes_the_pass(mesh):
    import jax.numpy as jnp

    from pslite_tpu.parallel.sparse import _acc_update_takes, _lookups

    config = _json("configs", "dlrm-dcnv2-multihot.json")
    B = _json("traffic", "zipf-bags-4096x214.json")["bags_per_table"]
    dim, hs = config["dim"], config["bag_sizes"]
    eng, tables, stores, accs = _engine(
        mesh, [(name, rows, dim) for name, rows in config["tables"]])
    k = len(tables)
    assert (k, B, dim, sum(hs)) == (26, 4096, 128, 214)
    batches = tuple(B if h == 1 else (B, h) for h in hs)
    # Kept in whole 128s: 20 of the 26 grow, by under 128 accumulators each,
    # and the six whose rows divide are what they were.
    kept = [t.acc_rows for t in tables]
    assert all(a % 128 == 0 and 0 <= a - t.num_rows < 128
               for a, t in zip(kept, tables))
    assert sum(a != t.num_rows for a, t in zip(kept, tables)) == 20
    assert sum(kept) - config["rows"] < 11 * 1024 // 4
    # The rule is the cost, and says yes for all 26; the engine counts so.
    assert all(_acc_update_takes(a, _lookups(b))
               for a, b in zip(kept, batches))
    idx = [_sds(mesh, (1, B, h), jnp.int32, "kv", None, None) for h in hs]
    grads = [_sds(mesh, (1, B, dim), jnp.float32, "kv", None, None)] * k
    scalar = jax.ShapeDtypeStruct((), np.float32)
    prog = eng._sparse_group_program("push_row_adagrad", tables, batches)
    compiled = prog.lower(*stores, *accs, *idx, *grads, scalar,
                          scalar).compile()
    mem = compiled.memory_analysis()
    # 26 stores + 26 accumulators donated and aliased (an accumulator's
    # tiling rounds it up by a few KiB).
    logical = config["rows"] * 516
    assert logical <= mem.alias_size_in_bytes < logical + 10**8, (
        mem.alias_size_in_bytes)
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"pooled push, 26 passes: temp {mem.temp_size_in_bytes:,} B, "
          f"peak {peak:,} B")
    assert peak + ROOM < BYTES_LIMIT, peak
    lines = _lines(compiled.as_text())
    kernels = [l for l in lines if l.startswith("%acc_update")]
    assert len(kernels) == k, len(kernels)
    assert all("tpu_custom_call" in l and "ps.update" in l for l in kernels)
    # One kernel a table, each over its own accumulator as 128-lane rows.
    assert sorted(a // 128 for a in kept) == sorted(
        int(l.split("(f32[")[1].split(",128]")[0]) for l in kernels)
    # No scatter into an accumulator and no gather of a batch's accumulators
    # is left (a table's write is ``row_add``'s, a custom call too).
    assert not [l for l in lines if " scatter(" in l
                and any(f"= f32[{a}]" in l for a in set(kept))]
    assert not [l for l in lines if " scatter(" in l and "ps.update" in l]
    # No copy of a donated accumulator.
    assert not [l for l in lines if " copy(" in l
                and any(f"= f32[{a}]" in l or f"= f32[{a // 128},128]" in l
                        for a in set(kept))]


def test_the_one_table_push_of_whole_128s_is_the_text_it_was(mesh,
                                                              monkeypatch):
    """``dlrm-criteo-rowadagrad.zipf``: 20,000,000 rows are whole 128s, the
    kept accumulator is the accumulator, and the engine's push compiles to
    the same text under the rule as it stood."""
    import jax.numpy as jnp

    from pslite_tpu.ops.acc_update import steps
    from pslite_tpu.parallel import sparse

    config = _json("configs", "dlrm-criteo-rowadagrad.json")
    n = _json("traffic", "zipf-rows-handle.json")["lookups_per_worker"]
    rows, dim = config["rows"], config["dim"]

    def as_it_stood(R, m):
        return (R % 128 == 0 and steps(R, m) * sparse._ACC_STEP_NS
                < m * sparse._ACC_SLOT_NS)

    def text(rule):
        monkeypatch.setattr(sparse, "_acc_update_takes", rule)
        eng, (table,), (store,), (acc,) = _engine(mesh,
                                                  [("emb", rows, dim)])
        assert acc.shape == (rows,) and eng._acc_kernel(table, n)
        prog = eng._sparse_program("push_row_adagrad", table, n)
        scalar = jax.ShapeDtypeStruct((), np.float32)
        return prog.lower(
            store, acc, _sds(mesh, (1, n), jnp.int32, "kv", None),
            _sds(mesh, (1, n, dim), jnp.float32, "kv", None, None),
            scalar, scalar).compile().as_text()

    # From one call site: a compiled text carries its stack frames.
    now, before = [text(rule)
                   for rule in (sparse._acc_update_takes, as_it_stood)]
    assert now == before
    assert sum(l.startswith("%acc_update") for l in _lines(now)) == 1
