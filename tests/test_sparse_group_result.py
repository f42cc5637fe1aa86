"""A grouped sparse pull allocates one result (PR 53): the rows of a group's
entries leave ``SparseEngine._sparse_group_program("pull", ...)`` as ONE array
``[W, sum n_i, d]`` a class of ``(d, dtype)``, and ``pull_group`` /
``KVWorker.get_pulled`` hand back a ``PulledGroup`` over them, a read-only
sequence whose entry ``i`` is cut from its class's array when asked for and not
before (a result is a buffer the runtime allocates at every launch, an eager cut
a launch of its own).

Held here, on one shard and on the four-CPU-device mesh
``test_sparse_four_servers.py`` uses, routed by owner, gathered, and routed with
a batch that falls back to the gathered body: a group of entries of two widths,
two dtypes and three batch sizes with one table named twice gives for every
entry the bits of the one-table ``pull`` of the same ids, through ``pull_group``
and through ``pull_sparse_group`` / ``get_pulled``; the program has one result a
class, and the array bare where there is one; ``outs=`` fills every table's
buffer from one host copy a class; ``_trim_results`` weighs the class arrays;
the LAUNCH note carries ``2k + classes``; nothing on the issue path, in ``wait``
or in the completion cuts an entry; a grouped op is bound once.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pslite_tpu import KVWorker  # noqa: E402
from pslite_tpu.parallel import sparse  # noqa: E402
from pslite_tpu.parallel.sparse import PulledGroup  # noqa: E402
from pslite_tpu.utils import profiling  # noqa: E402
from pslite_tpu.utils.profiling import LAUNCH, launched  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

ROWS = 203                  # no multiple of 4: the last shard short
# name: (width, dtype).  64 lanes are kept two to a physical row.
TABLES = {"a64": (64, np.float32), "b128": (128, np.float32),
          "c64": (64, np.float32), "h64": (64, jnp.bfloat16)}
# The call: (table, lookups a worker).  Two widths, two dtypes, three batch
# sizes, ``a64`` named twice.
CALL = [("a64", 48), ("b128", 16), ("c64", 32), ("h64", 48), ("a64", 16),
        ("b128", 32)]
NAMES = [name for name, _ in CALL]
BATCHES = tuple(n for _, n in CALL)
# A class of (width, dtype) in the order it first appears, and where each
# entry's rows lie in its class's array: (class, offset, n).
CLASSES = [(64, np.float32, 96), (128, np.float32, 48), (64, jnp.bfloat16, 48)]
ENTRIES = ((0, 0, 48), (1, 0, 16), (0, 48, 32), (2, 0, 48), (0, 80, 16),
           (1, 16, 32))


def _mesh(shards):
    return Mesh(np.array(jax.devices()[:shards]), ("kv",))


def _inits(seed=53):
    rng = np.random.default_rng(seed)
    return {name: np.asarray(jnp.asarray(
        rng.normal(size=(ROWS, dim)).astype(np.float32), dtype=dtype))
        for name, (dim, dtype) in TABLES.items()}


def _register(eng):
    inits = _inits()
    for name, (dim, dtype) in TABLES.items():
        eng.register_sparse(name, ROWS, dim, dtype=dtype, init=inits[name])
    return inits


def _spread(shards, seed=54):
    """Every worker's ids an even share to every owner (row ``r`` is shard
    ``r % S``'s), drawn from 50 rows so that they repeat, and one row every
    worker asks for: every (worker, owner) bucket holds its slots, so every
    exchange is routed where the mesh routes."""
    rng = np.random.default_rng(seed)
    idx = []
    for n in BATCHES:
        base = rng.integers(0, ROWS // shards, size=(shards, n))
        owner = (np.arange(n) + np.arange(shards)[:, None]) % shards
        idx.append((base * shards + owner).astype(np.int32))
        idx[-1][:, 0] = 7
    return idx


def _one_owner(shards, seed=55):
    """Every id of every worker on shard 1: no bucket holds them, the op
    takes the gathered body of the same program."""
    rng = np.random.default_rng(seed)
    idx = [(rng.integers(0, ROWS // shards, size=(shards, n)) * shards
            + 1).astype(np.int32) for n in BATCHES]
    assert max(i.max() for i in idx) < ROWS
    return idx


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


CASES = {
    # shards, the ids, whether the program threads a count, ops that fall back
    "one-shard-gathered": (1, _spread, False, 0),
    "four-shards-routed": (4, _spread, True, 0),
    "four-shards-overflow-to-gathered": (4, _one_owner, True, 1),
}


@pytest.fixture(params=list(CASES))
def case(request):
    shards, ids, routed, falls = CASES[request.param]
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.workers[0].van.set_mesh(_mesh(shards))
    c.start()
    kv = KVWorker(0, 0, postoffice=c.workers[0])
    eng = kv.po.van.sparse_engine
    inits = _register(eng)
    assert eng._group_routed(BATCHES) == routed
    yield kv, eng, inits, ids(shards), shards, routed, falls
    c.finalize()


def _is_the_one_table_pull(eng, inits, idx, pulled):
    """Every entry of ``pulled``: the stored rows, and the bits of
    ``SparseEngine.pull`` of the same ids, a worker's batch a device."""
    assert type(pulled) is PulledGroup and len(pulled) == len(CALL)
    assert pulled.entries == ENTRIES
    S = eng.num_shards
    assert [(a.shape, a.dtype) for a in pulled.arrays] == [
        ((S, n, d), np.dtype(dtype)) for d, dtype, n in CLASSES]
    want = NamedSharding(eng.mesh, P(eng.axis, None, None))
    assert all(a.sharding.is_equivalent_to(want, 3) for a in pulled.arrays)
    cut = list(pulled)
    assert len(cut) == len(CALL)
    for i, ((name, n), ids) in enumerate(zip(CALL, idx)):
        dim, dtype = TABLES[name]
        one = eng.pull(name, ids)
        for rows in (cut[i], pulled[i]):
            assert rows.shape == (S, n, dim) and rows.dtype == np.dtype(dtype)
            assert (_bits(rows) == _bits(one)).all(), (i, name)
        assert (_bits(cut[i]) == _bits(inits[name][ids])).all(), (i, name)


def test_pull_group_gives_every_entry_its_one_table_pulls_bits(case):
    _, eng, inits, idx, _, _, falls = case
    before = eng.route_overflows()
    pulled = eng.pull_group(NAMES, idx)
    assert eng.route_overflows() == before + falls
    _is_the_one_table_pull(eng, inits, idx, pulled)
    # The same record and the same program again: the same bits.
    again = eng.pull_group(NAMES, idx)
    assert all((_bits(a) == _bits(b)).all()
               for a, b in zip(pulled.arrays, again.arrays))


def test_get_pulled_of_a_grouped_pull_is_the_sequence(case):
    kv, eng, inits, idx, *_ = case
    ts = kv.pull_sparse_group(NAMES, idx)
    kv.wait(ts)
    _is_the_one_table_pull(eng, inits, idx, kv.get_pulled(ts))


def test_the_program_has_one_result_a_class(case):
    _, eng, _, idx, shards, routed, _ = case
    tables = [eng._tables[n] for n in NAMES]
    assert sparse._group_entries(tables, BATCHES) == (ENTRIES, len(CLASSES))
    prog = eng._sparse_group_program("pull", tables, BATCHES)
    placed = [eng._prep_ids(i) for i in idx]
    count = [eng._overflow_count(NAMES[0])] * routed
    lowered = prog.lower(*[eng._stores[n] for n in NAMES], *placed, *count)
    outs = jax.tree_util.tree_leaves(lowered.out_info)
    assert [(tuple(o.shape), o.dtype) for o in outs] == [
        ((shards, n, d), np.dtype(dtype)) for d, dtype, n in CLASSES
    ] + [((shards,), np.dtype(np.int32))] * routed
    # One class and no count (a lookup a worker is routed nowhere): the
    # array bare, as the one-table pull's; one table through ``pull_group``
    # takes the grouped form, with one class.
    for names in (["a64", "c64", "a64"], ["b128"]):
        k = len(names)
        assert not eng._group_routed((1,) * k)
        prog = eng._sparse_group_program(
            "pull", [eng._tables[x] for x in names], (1,) * k)
        one = [eng._prep_ids(np.full((shards, 1), 7, np.int32))] * k
        info = prog.lower(*[eng._stores[x] for x in names], *one).out_info
        assert not isinstance(info, (tuple, list))
        assert tuple(info.shape) == (shards, k, TABLES[names[0]][0])
        pulled = eng.pull_group(names, one)
        assert type(pulled) is PulledGroup and len(pulled.arrays) == 1
        assert pulled.entries == tuple((0, i, 1) for i in range(k))
        assert all((_bits(rows) == _bits(eng.pull(x, one[0]))).all()
                   for x, rows in zip(names, pulled))


def test_outs_fills_every_buffer_from_one_host_copy_a_class(case,
                                                            monkeypatch):
    kv, eng, inits, idx, shards, *_ = case
    outs = [np.full((shards, n, TABLES[name][0]), np.nan, np.float32)
            for name, n in CALL]
    copied = []
    host_rows = kv._host_rows
    monkeypatch.setattr(kv, "_host_rows",
                        lambda r: copied.append(r.shape) or host_rows(r))
    done = []
    ts = kv.pull_sparse_group(NAMES, idx, outs=outs,
                              callback=lambda: done.append(1))
    kv.wait(ts)
    assert done == [1]
    assert copied == [(shards, n, d) for d, _, n in CLASSES]
    for (name, _), ids, out in zip(CALL, idx, outs):
        # (a bf16 table's rows widened into the caller's f32 buffer)
        assert (out == inits[name][ids].astype(np.float32)).all(), name
    with pytest.raises(Exception, match="one host buffer a table"):
        kv.pull_sparse_group(NAMES, idx, outs=outs[:2])


def test_trim_results_weighs_the_class_arrays(case, monkeypatch):
    kv, eng, _, idx, shards, *_ = case
    weight = shards * sum(n * d * np.dtype(dtype).itemsize
                          for d, dtype, n in CLASSES)
    stamps = []
    for _ in range(3):
        ts = kv.pull_sparse_group(NAMES, idx)
        kv.wait(ts)
        stamps.append(ts)
    assert sum(a.nbytes for a in kv.get_pulled(stamps[-1]).arrays) == weight
    monkeypatch.setattr(kv, "_results_heavy", True)
    monkeypatch.setattr(kv, "_DEVICE_RESULTS_BYTES", int(2.5 * weight))
    ts = kv.pull_sparse_group(NAMES, idx)
    kv.wait(ts)
    assert [s for s in stamps + [ts] if kv.get_pulled(s) is not None] \
        == [stamps[-1], ts]


def test_the_launch_note_carries_2k_plus_classes(case, monkeypatch):
    _, eng, _, idx, _, routed, _ = case
    eng.pull_group(NAMES, idx)              # binds
    notes = []
    monkeypatch.setattr(eng, "_note", notes.append)
    eng.pull_group(NAMES, idx)
    arrays = 2 * len(CALL) + len(CLASSES) + 2 * routed
    assert [n[4] for n in notes if n[0] == LAUNCH] == [
        launched("sparse.pull", arrays)]
    record = eng._bound[("pull", tuple(NAMES), None, BATCHES)]
    assert record.launched == launched("sparse.pull", arrays)
    assert record.entries == ENTRIES


def test_nothing_cuts_an_entry_until_it_is_asked_for(case, monkeypatch):
    kv, eng, _, idx, shards, *_ = case
    cuts = []
    cut = PulledGroup.__getitem__
    monkeypatch.setattr(PulledGroup, "__getitem__",
                        lambda self, i: cuts.append(i) or cut(self, i))
    clock = profiling.stage_clock()
    outs = [np.zeros((shards, n, TABLES[name][0]), np.float32)
            for name, n in CALL]
    kv.wait(kv.pull_sparse_group(NAMES, idx))       # binds
    kv.wait(kv.pull_sparse_group(NAMES, idx, outs=outs))
    launches = clock.launches_totals()["sparse.pull"][0]
    stamps = [kv.pull_sparse_group(NAMES, idx),
              kv.pull_sparse_group(NAMES, idx, outs=outs),
              kv.pull_sparse_group(NAMES, idx, callback=lambda: None)]
    for ts in stamps:
        kv.wait(ts)
        kv.wait(ts)
    pulled = kv.get_pulled(stamps[0])
    assert (len(pulled), len(pulled.arrays)) == (len(CALL), len(CLASSES))
    assert cuts == []
    # Three ops, three launches noted: nothing else was issued for them.
    assert clock.launches_totals()["sparse.pull"][0] == launches + 3
    assert pulled[4].shape == (shards, 16, 64) and cuts == [4]
    list(pulled)
    assert cuts == [4, *range(len(CALL))]


def test_a_run_of_grouped_pulls_and_pushes_binds_two_records(case):
    kv, eng, _, idx, shards, *_ = case
    once = [i for i, name in enumerate(NAMES) if NAMES.index(name) == i]
    names = [NAMES[i] for i in once]
    grads = [np.ones((shards, BATCHES[i], TABLES[NAMES[i]][0]), np.float32)
             for i in once]
    gauge = kv.po.metrics.snapshot
    before = gauge()["gauges"]["engine.sparse.group.binds"]
    for _ in range(3):
        ts = kv.pull_sparse_group(NAMES, idx)
        kv.wait(kv.push_sparse_group(names, [idx[i] for i in once], grads))
        kv.wait(ts)
    assert gauge()["gauges"]["engine.sparse.group.binds"] == before + 2
    assert eng.group_binds == before + 2


def test_the_sequence_is_read_only_and_a_pytree_of_its_arrays(case):
    _, eng, _, idx, *_ = case
    pulled = eng.pull_group(NAMES, idx)
    with pytest.raises(TypeError):
        pulled[0] = pulled[1]
    with pytest.raises(AttributeError):
        pulled.extra = 1
    assert type(pulled.arrays) is tuple and type(pulled.entries) is tuple
    # A jitted forward pass takes the sequence whole and cuts inside, where a
    # slice is free: the arrays are its leaves, the entries static.
    leaves, tree = jax.tree_util.tree_flatten(pulled)
    assert all(a is b for a, b in zip(leaves, pulled.arrays))
    assert len(leaves) == len(CLASSES)
    back = jax.tree_util.tree_unflatten(tree, leaves)
    assert type(back) is PulledGroup and back.entries == ENTRIES
    assert jax.block_until_ready(pulled) is pulled

    @jax.jit
    def forward(rows):
        return [r.astype(jnp.float32).sum(axis=-1) for r in rows]

    for got, rows in zip(forward(pulled), pulled):
        want = np.asarray(rows).astype(np.float32).sum(axis=-1)
        assert np.allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
