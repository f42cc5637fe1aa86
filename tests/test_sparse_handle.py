"""A stateful server handle on sparse rows, reached through
``KVWorker.push_sparse(name, indices, grads, handle)`` -> ``_engine_op`` ->
``SparseEngine.push``: row-wise Adagrad against a plain float64 reference,
the plain sum left as it was, the record a ``(table, handle, batch)`` is
bound to, the counters and the span that say a push ran under a handle, the
combine both pushes share (``_combine_rows``) and the sum written by distinct
row where ``ops/row_add.py`` takes the table's rows.
"""

import glob
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh  # noqa: E402

from pslite_tpu import KVWorker  # noqa: E402
from pslite_tpu.parallel.sparse import SparseEngine  # noqa: E402
from pslite_tpu.utils import logging as log  # noqa: E402
from pslite_tpu.utils import profiling  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

LR, EPS = 0.05, 1e-8
HANDLE = f"row_adagrad:{LR},{EPS}"
ROWS = 61          # no multiple of 4, nor of the packing factor


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("kv",))


@pytest.fixture()
def cluster(request):
    """A worker whose ici van holds ``W`` devices (default: all eight)."""
    W = getattr(request, "param", None)
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    if W is not None:
        c.workers[0].van.set_mesh(_mesh(W))
    c.start()
    kv = KVWorker(0, 0, postoffice=c.workers[0])
    yield kv, kv.po.van.sparse_engine
    c.finalize()


# -- the plain reference ------------------------------------------------------


def bf16(x):
    """Round to the nearest bfloat16, as float64."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                        & np.uint32(1))) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


class RowAdagrad:
    """The recurrence as ``parallel/sparse.py`` documents it, on a whole
    small table in float64: per push, G = the sum of every gradient the
    push brings to a row (duplicates within and across workers), ``acc +=
    mean(G**2)``, ``row -= lr * G / (sqrt(acc) + eps)``; a row the push
    does not bring anything to is neither read nor written."""

    def __init__(self, init, rounding=None):
        self.rd = rounding or (lambda x: x)
        self.table = np.asarray(init, np.float64).copy()
        self.acc = np.zeros(len(self.table), np.float64)

    def push(self, idx, grads):
        G = np.zeros_like(self.table)
        np.add.at(G, np.asarray(idx).reshape(-1),
                  np.asarray(grads, np.float64).reshape(-1, G.shape[1]))
        rows = np.unique(idx)
        G = self.rd(G)
        self.acc[rows] = self.rd(self.acc[rows]
                                 + np.mean(G[rows] ** 2, axis=1))
        self.table[rows] = self.rd(
            self.table[rows] - LR * G[rows]
            / (np.sqrt(self.acc[rows])[:, None] + EPS))


def _traffic(W, dim, seed=3):
    """Three pushes of ``[W, 12]`` rows: row 0 from every worker (the
    hottest), a duplicate within each worker, rows shared across workers,
    and rows 50.. that nothing touches."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(1, 50, size=(W, 12)).astype(np.int32)
    idx[:, 0] = 0                       # every worker hits the hottest row
    idx[:, 1] = idx[:, 2]               # a duplicate within a worker
    idx[:, 3] = 7                       # the same row across workers
    init = rng.normal(size=(ROWS, dim)).astype(np.float32)
    grads = [rng.normal(size=(W, 12, dim)).astype(np.float32)
             for _ in range(3)]
    return idx, init, grads


def _row_error(got, want):
    """Worst |got - want| in a row over the row's largest |want| (or one
    learning rate: a row is of the size of its steps)."""
    diff = np.abs(np.asarray(got, np.float64) - want).max(axis=-1)
    return float((diff / np.maximum(np.abs(want).max(axis=-1), LR)).max())


# Why 2e-5: the device works in f32 (sum of at most 2W gradients, a mean of
# squares, one sqrt and one division a push: a few ulp of 6e-8 each, three
# pushes), the reference in float64; bf16 anywhere in the recurrence is 4e-3
# a value, two hundred times the limit (asserted below).
TOL = 2e-5


@pytest.mark.parametrize("dim", [8, 128, 256])   # lane-packed and not
@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_three_pushes_then_a_pull_match_the_reference(cluster, dim):
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, dim)
    eng.register_sparse("emb", ROWS, dim, init=init)
    ref = RowAdagrad(init)
    for g in grads:
        ts = kv.push_sparse("emb", idx, g, HANDLE)
        ref.push(idx, g)
    kv.wait(ts)
    everything = np.tile(np.arange(ROWS, dtype=np.int32), (W, 1))
    out = np.zeros((W, ROWS, dim), np.float32)
    kv.wait(kv.pull_sparse("emb", everything, out=out))
    assert _row_error(out[0], ref.table) < TOL
    assert (out == out[0]).all()        # every worker reads the one table
    # A row no push touched is bit-unchanged; its accumulator is zero.
    touched = np.unique(idx)
    quiet = np.setdiff1d(np.arange(ROWS), touched)
    assert len(quiet) >= 10 and (out[0][quiet] == init[quiet]).all()
    acc = np.asarray(eng.acc_global_device("emb"))
    assert (acc[quiet] == 0).all() and (acc[touched] > 0).all()
    np.testing.assert_allclose(acc, ref.acc, rtol=1e-5)
    # The accumulator is sharded like the table: 1/W of it a device, the
    # shard's rows in whole 128s.
    shards = eng._acc["emb"].addressable_shards
    kept = eng.table("emb").acc_rows
    assert kept == -(-eng.table("emb").rows_per_shard // 128) * 128
    assert len(shards) == W and {s.data.shape for s in shards} == {(kept,)}
    assert len({s.device for s in shards}) == W
    # The order of pushes matters, and bf16 arithmetic fails the limit.
    swapped = RowAdagrad(init)
    for g in reversed(grads):
        swapped.push(idx, g)
    assert _row_error(out[0], swapped.table) > 100 * TOL
    rounded = RowAdagrad(init, bf16)
    for g in grads:
        rounded.push(idx, g)
    assert _row_error(rounded.table, ref.table) > 100 * TOL


@pytest.mark.parametrize("dim", [64, 128])
@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_inputs_that_lie_as_the_program_takes_them_are_passed_on(cluster, dim):
    """``_prep`` hands a device array of the worker axis' sharding and the
    table's dtype to the program as it is (no cast, no placement), takes
    every other input the way it did, and the table ends the same."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, dim)
    table = eng.register_sparse("emb", ROWS, dim, init=init)
    eng.register_sparse("host", ROWS, dim, init=init)
    idx_d = jax.device_put(idx, NamedSharding(eng.mesh, P(eng.axis, None)))
    g_d = jax.device_put(
        grads[0], NamedSharding(eng.mesh, P(eng.axis, None, None)))
    got_idx, got_g = eng._prep(table, idx_d, g_d)
    assert got_idx is idx_d and got_g is g_d
    # Another dtype, another layout or the host: cast and placed as before.
    for other in (idx.astype(np.int64), jnp.asarray(idx), idx):
        placed, _ = eng._prep(table, other)
        assert placed is not other and placed.dtype == jnp.int32
        assert placed.sharding.is_equivalent_to(idx_d.sharding, 2)
        assert (np.asarray(placed) == idx).all()
    kv.wait(kv.push_sparse("emb", idx_d, g_d))
    kv.wait(kv.push_sparse("host", idx, grads[0]))
    assert (np.asarray(eng.store_global_device("emb"))
            == np.asarray(eng.store_global_device("host"))).all()
    ts = kv.pull_sparse("emb", idx_d)
    pulled = np.asarray(kv.get_pulled(ts))
    kv.wait(ts)
    out = np.zeros((W, idx.shape[1], dim), np.float32)
    kv.wait(kv.pull_sparse("host", idx, out=out))
    assert (pulled == out).all()
    with pytest.raises(log.CheckError, match="bad worker dim"):
        eng._prep(table, jnp.zeros((W + 1, 4), jnp.int32))


@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_a_push_with_no_handle_is_the_plain_sum_it_was(cluster):
    """The same ``_programs`` key, and bit for bit what
    ``SparseEngine.push`` of an engine of its own gives."""
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, 128)
    table = eng.register_sparse("emb", ROWS, 128, init=init)
    twin = SparseEngine(eng.mesh, eng.axis)
    twin.register_sparse("emb", ROWS, 128, init=init)
    for g in grads:
        ts = kv.push_sparse("emb", idx, g)
        twin.push("emb", idx, g)
    kv.wait(ts)
    key = ("push", "emb", idx.shape[1], table.pack)
    assert [k for k in eng._programs if k[0].startswith("push")] == [key]
    assert key in twin._programs
    assert "emb" not in eng._acc and eng.stateful_pushes == 0
    assert (eng.store_array("emb") == twin.store_array("emb")).all()
    want = np.zeros((ROWS, 128), np.float64)
    for g in grads:
        np.add.at(want, idx.reshape(-1),
                  g.astype(np.float64).reshape(-1, 128))
    got = np.asarray(eng.store_global_device("emb"))
    np.testing.assert_allclose(got, init + want, rtol=1e-5, atol=1e-5)


def _workspaces(W, batch):
    """The slots a shard's push program of ``batch`` lookups a worker is
    traced at: its bound body's (``S * C`` routed by owner over several
    shards) and the gathered body's ``W * batch`` it keeps for the batch
    that does not fit; one and the same on one shard."""
    from pslite_tpu.parallel import sparse

    return {sparse._slots(W, batch), W * batch}


def _kernel_on_cpu(monkeypatch):
    """Name the CPU among the platforms whose programs write a table with
    ``ops/row_add.py`` (interpreted there), from here to the test's end;
    returns the list that takes the shape of every table the kernel is
    traced with."""
    from pslite_tpu.ops import row_add as row_add_module
    from pslite_tpu.parallel import sparse

    traced = []
    real = row_add_module.row_add
    monkeypatch.setattr(
        row_add_module, "row_add",
        lambda store, *a, **kw: traced.append(store.shape) or real(
            store, *a, **kw))
    monkeypatch.setitem(sparse._ROW_ADD_INTERPRET, "cpu", True)
    return traced


@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_the_row_kernel_in_the_push_is_bit_equal_to_xlas_scatter(
        cluster, monkeypatch):
    """On the CPU a stateful push writes the table with XLA's scatter; the
    test names the CPU among the kernel's platforms (interpreted) and the
    same three pushes give the same bits, rows and accumulator.  The
    counter follows the program: whole 128-lane f32 rows under a handle."""
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, 128)
    twin = SparseEngine(eng.mesh, eng.axis)
    twin.register_sparse("emb", ROWS, 128, init=init)
    for g in grads:
        twin.push("emb", idx, g, HANDLE)
    assert (twin.stateful_pushes, twin.row_kernel_pushes) == (3, 0)

    traced = _kernel_on_cpu(monkeypatch)
    eng.register_sparse("emb", ROWS, 128, init=init)
    packed = eng.register_sparse("packed", ROWS, 8, init=init[:, :8])
    # The rule speaks of the PHYSICAL row: a lane-packed table keeps 16
    # rows of 8 lanes in one of 128 and takes the kernel; a row wider than
    # one tile keeps the scatter (Mosaic refuses the kernel's one-row slice
    # of it: ops/row_add.py), and so does a table that is not f32.
    wide = eng.register_sparse("wide", ROWS, 256)
    half = eng.register_sparse("half", ROWS, 64, dtype=jax.numpy.bfloat16)
    assert eng._row_kernel(eng.table("emb")) and eng._row_kernel(packed)
    assert not eng._row_kernel(wide) and not eng._row_kernel(half)
    assert (packed.pack, half.pack, wide.pack) == (16, 2, 1)
    for g in grads:
        ts = kv.push_sparse("emb", idx, g, HANDLE)
    kv.wait(ts)
    rps = eng.table("emb").rows_per_shard
    assert traced and set(traced) == {(rps, 128)}       # in the program
    assert (eng.store_array("emb") == twin.store_array("emb")).all()
    assert (np.asarray(eng._acc["emb"]) == np.asarray(twin._acc["emb"])).all()
    assert _gauges(kv)["engine.sparse.push.row_kernel"] == 3
    assert _gauges(kv)["engine.sparse.push.packed"] == 0
    # The CPU is named for ``row_add`` alone: the duplicates were summed by
    # XLA's scatter-add, in its order, which is why the bits are equal.
    assert _gauges(kv)["engine.sparse.push.segsum_kernel"] == 0
    # A lane-packed table's push is written by the kernel too, by physical
    # row; a push with no handle whose program writes through the kernel is
    # counted like one under a handle.
    kv.wait(kv.push_sparse("packed", idx, grads[0][..., :8], HANDLE))
    assert set(traced) == {(rps, 128), (packed.phys_rows, 128)}
    assert _gauges(kv)["engine.sparse.push.row_kernel"] == 4
    assert _gauges(kv)["engine.sparse.push.packed"] == 1
    kv.wait(kv.push_sparse("emb", idx, grads[0]))
    after = _gauges(kv)
    assert after["engine.sparse.push.stateful"] == 4
    assert after["engine.sparse.push.row_kernel"] == 5
    assert after["engine.sparse.push.packed"] == 1
    assert after["engine.sparse.push.segsum_kernel"] == 0


def _both_kernels_on_cpu(monkeypatch):
    """:func:`_kernel_on_cpu`, and the CPU named among the platforms whose
    programs sum a combine's segments with ``ops/segment_sum.py`` too: the
    lowering rule of a TPU, interpreted.  Returns the two lists of shapes,
    ``row_add``'s tables and ``segment_sum``'s batches."""
    from pslite_tpu.ops import segment_sum as segment_sum_module
    from pslite_tpu.parallel import sparse

    summed = []
    real = segment_sum_module.segment_sum
    monkeypatch.setattr(
        segment_sum_module, "segment_sum",
        lambda seg, sg, **kw: summed.append(sg.shape) or real(seg, sg, **kw))
    monkeypatch.setitem(sparse._SEGMENT_SUM_INTERPRET, "cpu", True)
    return _kernel_on_cpu(monkeypatch), summed


@pytest.mark.parametrize("handle", [None, HANDLE], ids=["sum", "row_adagrad"])
@pytest.mark.parametrize("dim", [64, 128, 256])
@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_the_segment_sum_kernel_in_the_push_is_counted_and_matches(
        cluster, dim, handle, monkeypatch):
    """On the CPU mesh a push sums its duplicates with XLA's scatter-add and
    ``engine.sparse.push.segsum_kernel`` stays 0; under a TPU's lowering
    rule (the CPU named for both kernels, interpreted) the same three pushes
    sum them with ``ops/segment_sum.py`` wherever the combined rows are 128
    f32 lanes (an unpacked 128-wide table's; a lane-packed table's by
    physical row, under ``row_adagrad`` its merge and not its first combine
    by logical row), the counter equals the pushes, and the table is the
    reference's and, to f32 rounding, the scatter path's.  A 256-wide table
    keeps XLA's in both."""
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _packed_traffic(W, dim)

    def run(engine):
        engine.register_sparse("emb", ROWS, dim, init=init)
        for g in grads:
            token = engine.push("emb", idx, g, handle)
        token.block_until_ready()

    twin = SparseEngine(eng.mesh, eng.axis)
    run(twin)                               # the CPU's own: XLA's
    assert (twin.segsum_kernel_pushes, twin.row_kernel_pushes) == (0, 0)
    assert not twin._bound[("emb", handle, idx.shape[1])].segsum_kernel
    written, summed = _both_kernels_on_cpu(monkeypatch)
    eng.register_sparse("emb", ROWS, dim, init=init)
    for g in grads:
        ts = kv.push_sparse("emb", idx, g, handle)
    kv.wait(ts)
    table = eng.table("emb")
    served = dim != 256
    # Both bodies of the program are traced: the routed one's S x C slots
    # and, for a batch that does not fit, the gathered one's W x n.
    assert set(summed) == ({(m, 128) for m in _workspaces(W, idx.shape[1])}
                           if served else set())
    assert set(written) == ({(table.phys_rows, 128)} if served else set())
    gauges = _gauges(kv)
    assert gauges["engine.sparse.push.segsum_kernel"] == 3 * served
    assert gauges["engine.sparse.push.row_kernel"] == 3 * served
    assert eng._bound[("emb", handle, idx.shape[1])].segsum_kernel == served
    got = np.asarray(eng.store_global_device("emb"))
    scattered = np.asarray(twin.store_global_device("emb"))
    if handle is None:
        np.testing.assert_allclose(got, _sum_reference(init, idx, grads),
                                   rtol=1e-5, atol=1e-5)
    else:
        ref = RowAdagrad(init)
        for g in grads:
            ref.push(idx, g)
        assert _row_error(got, ref.table) < TOL
        np.testing.assert_allclose(np.asarray(eng._acc["emb"]),
                                   np.asarray(twin._acc["emb"]), rtol=1e-5)
    np.testing.assert_allclose(got, scattered, rtol=1e-5, atol=1e-5)
    quiet = np.setdiff1d(np.arange(ROWS), np.unique(idx))
    assert (got[quiet] == init[quiet]).all() and np.isfinite(got).all()
    # The segment sum named alone: the sum combines only where ``row_add``
    # follows, a handle's combine by logical row takes 128-wide rows only.
    from pslite_tpu.parallel import sparse

    monkeypatch.delitem(sparse._ROW_ADD_INTERPRET, "cpu")
    assert eng._segsum_kernel(table, True) == (dim == 128)
    assert not eng._segsum_kernel(table, False)


def _acc_kernel_on_cpu(monkeypatch):
    """Name the CPU among the platforms whose stateful pushes update the
    accumulator with ``ops/acc_update.py`` (interpreted there); returns the
    list that takes ``(accumulators, slots)`` of every trace of the kernel."""
    from pslite_tpu.ops import acc_update as acc_update_module
    from pslite_tpu.parallel import sparse

    traced = []
    real = acc_update_module.acc_update
    monkeypatch.setattr(
        acc_update_module, "acc_update",
        lambda acc, rows, *a, **kw: traced.append(
            (acc.shape[0], rows.shape[0])) or real(acc, rows, *a, **kw))
    monkeypatch.setitem(sparse._ACC_UPDATE_INTERPRET, "cpu", True)
    return traced


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["single", "grouped"])
@pytest.mark.parametrize("table_rows", [None, 1003],
                         ids=["whole-128s", "1003-rows"])
@pytest.mark.parametrize("dim", [64, 128, 256],
                         ids=["lane-packed", "unpacked", "256-wide"])
@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_the_accumulator_kernel_in_the_push_is_counted_and_bit_equal(
        cluster, dim, table_rows, grouped, monkeypatch):
    """On the CPU mesh a stateful push updates the accumulator with XLA's
    1-D gather and scatter and ``engine.sparse.push.acc_kernel`` stays 0;
    with the CPU named among ``acc_update``'s platforms (interpreted; alone,
    so that sums and table writes keep XLA's order) the same three pushes
    with duplicates leave the same bits in table and accumulator, whatever the
    table's width or packing (the accumulator is by logical row), and the
    counter equals the pushes, a group counting as one, while
    ``engine.sparse.push.acc_kernel_tables`` counts a group's tables.  A
    table of 1,003 rows, no multiple of 128 on any mesh, takes the pass as
    one of 128 a shard does: its accumulator is kept in whole 128s and the
    tail behind a shard's rows stays zero bit for bit.  The plain sum, and
    a batch too small for a pass over the accumulator to pay, are not
    counted and keep XLA's."""
    from pslite_tpu.parallel import sparse

    kv, eng = cluster
    W = eng.num_shards
    # 128 accumulators a shard, one row of them; or 1,003 rows in all.
    rows, batch = table_rows or 128 * W, 64
    rng = np.random.default_rng(dim + W)
    idx = rng.integers(0, rows, size=(W, batch)).astype(np.int32)
    idx[:, 0], idx[:, 1] = 0, rows - 1  # a shard's first and last, by all
    idx[:, 2] = idx[:, 3]               # a duplicate within a worker
    init = rng.normal(size=(rows, dim)).astype(np.float32)
    grads = [rng.normal(size=(W, batch, dim)).astype(np.float32)
             for _ in range(3)]
    names = ["emb", "other"] if grouped else ["emb"]

    def run(engine):
        for name in names:
            engine.register_sparse(name, rows, dim, init=init)
        for g in grads:
            if grouped:
                token = engine.push_group(names, [idx] * 2, [g, 2 * g],
                                          handle=HANDLE)
            else:
                token = engine.push("emb", idx, g, HANDLE)
        token.block_until_ready()

    twin = SparseEngine(eng.mesh, eng.axis)
    run(twin)                               # the CPU's own: XLA's
    assert (twin.stateful_pushes, twin.acc_kernel_pushes) == (3, 0)
    assert twin.acc_kernel_tables == 0
    traced = _acc_kernel_on_cpu(monkeypatch)
    run(eng)
    rps, kept = eng.table("emb").rows_per_shard, eng.table("emb").acc_rows
    assert kept == -(-rps // 128) * 128 and (kept == rps) == (not table_rows)
    assert set(traced) == {(kept, m) for m in _workspaces(W, batch)}
    assert (eng.stateful_pushes, eng.acc_kernel_pushes) == (3, 3)
    assert eng.acc_kernel_tables == 3 * len(names)
    assert _gauges(kv)["engine.sparse.push.acc_kernel"] == 3
    assert _gauges(kv)["engine.sparse.push.acc_kernel_tables"] == 3 * len(
        names)
    if not grouped:
        assert eng._bound[("emb", HANDLE, batch)].acc_kernel
        assert eng._bound[("emb", HANDLE, batch)].acc_tables == 1
        assert not twin._bound[("emb", HANDLE, batch)].acc_kernel
    for name in names:
        assert (eng.store_array(name) == twin.store_array(name)).all()
        acc = np.asarray(eng._acc[name])
        assert (acc.view(np.uint32)
                == np.asarray(twin._acc[name]).view(np.uint32)).all()
        assert acc.shape == (kept * W,)
        assert (acc > 0).sum() == len(np.unique(idx))
        # The tail no id names: zero, bit for bit.
        assert not acc.view(np.uint32).reshape(W, kept)[:, rps:].any()
        by_row = np.asarray(eng.acc_global_device(name))
        assert by_row.shape == (rows,) and (by_row[np.unique(idx)] > 0).all()
    # The plain sum has no accumulator; a batch of 12 slots a worker keeps
    # XLA's (the pass costs by the accumulator, the gather by the slot).
    del traced[:]
    eng.push("emb", idx, grads[0]).block_until_ready()
    eng.push("emb", idx[:, :12], grads[0][:, :12], HANDLE).block_until_ready()
    assert not traced and eng.acc_kernel_pushes == 3
    assert eng.stateful_pushes == 4
    assert not eng._acc_kernel(eng.table("emb"), 12)
    assert not sparse._acc_update_takes(kept, 12 * W)
    # The rule on shapes alone: the cell's, and a small batch into its
    # table.  It is a cost: no row count is refused.
    assert sparse._acc_update_takes(20_000_000, 131_072)
    assert not sparse._acc_update_takes(20_000_000, 4_096)
    assert sparse._acc_update_takes(sparse._acc_rows(20_000_001), 131_072)
    assert sparse._acc_rows(20_000_001) == 20_000_128


@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_the_accumulator_kernel_under_distinct_rows_of_a_tables_first_tile(
        cluster, monkeypatch):
    """Every slot of the batch a distinct row and all of them on the first
    shard, in the first of its accumulator's two tiles: the kernel's last
    chunk of ids is live and ends below the last tile, so the walk passes
    the tile that follows under it.  Table and accumulator are XLA's bit
    for bit over two pushes, the second onto accumulators that are not
    zero, at the kernel's own tile and chunk and by ``_acc_update_takes``'s
    own reckoning."""
    from pslite_tpu.ops import acc_update as acc_update_module

    kv, eng = cluster
    W = eng.num_shards
    per_shard = 2 * acc_update_module._TILE_ROWS * 128
    rows, batch, dim = per_shard * W, acc_update_module._CHUNK, 128
    # Row ``r`` is shard ``r % W``'s row ``r // W``.
    idx = (W * np.arange(W * batch, dtype=np.int32)).reshape(W, batch)
    rng = np.random.default_rng(W)
    init = np.zeros((rows, dim), np.float32)
    init[idx.reshape(-1)] = rng.normal(size=(W * batch, dim))
    grads = [rng.normal(size=(W, batch, dim)).astype(np.float32)
             for _ in range(2)]

    def run(engine):
        engine.register_sparse("emb", rows, dim, init=init)
        for g in grads:
            token = engine.push("emb", idx, g, HANDLE)
        token.block_until_ready()

    twin = SparseEngine(eng.mesh, eng.axis)
    run(twin)
    traced = _acc_kernel_on_cpu(monkeypatch)
    run(eng)
    # Over several shards the batch, all of one owner, does not fit the
    # routed exchange's buckets: both pushes run the gathered body, where
    # the kernel walks the W x batch slots this test is about (the counter
    # goes by the bound body's slots, which take the kernel as well).
    assert eng.route_overflows() == 2 * eng._routed(batch)
    assert set(traced) == {(per_shard, m) for m in _workspaces(W, batch)}
    assert (eng.acc_kernel_pushes, twin.acc_kernel_pushes) == (2, 0)
    assert (eng.store_array("emb") == twin.store_array("emb")).all()
    acc = np.asarray(eng._acc["emb"])
    assert (acc == np.asarray(twin._acc["emb"])).all()
    by_row = np.asarray(eng.acc_global_device("emb"))
    assert (by_row[idx.reshape(-1)] > 0).all()
    assert np.count_nonzero(by_row) == W * batch


def _sum_reference(init, idx, grads):
    want = np.asarray(init, np.float64).copy()
    for g in grads:
        np.add.at(want, idx.reshape(-1),
                  g.astype(np.float64).reshape(-1, want.shape[1]))
    return want


@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_the_sum_push_by_distinct_row_matches_the_reference(
        cluster, monkeypatch):
    """With the CPU named among the kernel's platforms a push with no
    handle combines its duplicates and writes each distinct row once
    (``row_add``, interpreted): the float64 sum at the scatter's own
    tolerance, a hot row read alike by every worker, and the counter."""
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, 128)
    traced = _kernel_on_cpu(monkeypatch)
    table = eng.register_sparse("emb", ROWS, 128, init=init)
    for g in grads:
        ts = kv.push_sparse("emb", idx, g)
    kv.wait(ts)
    assert set(traced) == {(table.rows_per_shard, 128)}  # in the program
    assert "emb" not in eng._acc
    got = np.asarray(eng.store_global_device("emb"))
    np.testing.assert_allclose(got, _sum_reference(init, idx, grads),
                               rtol=1e-5, atol=1e-5)
    # Rows no push touched are bit-unchanged.
    quiet = np.setdiff1d(np.arange(ROWS), np.unique(idx))
    assert len(quiet) >= 10 and (got[quiet] == init[quiet]).all()
    # Every copy of the hottest row in the next pull is the same bits.
    out = np.zeros((W, idx.shape[1], 128), np.float32)
    kv.wait(kv.pull_sparse("emb", idx, out=out))
    assert (out[:, 0] == out[0, 0]).all() and (out[0, 0] == got[0]).all()
    assert (out[:, 1] == out[:, 2]).all()       # the duplicate in a worker
    gauges = _gauges(kv)
    assert gauges["engine.sparse.push.stateful"] == 0
    assert gauges["engine.sparse.push.row_kernel"] == 3
    assert eng._bound[("emb", None, idx.shape[1])].row_kernel


@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_packed_tables_take_the_kernel_wide_ones_the_scatter_and_a_group_follows_its_tables(
        cluster, monkeypatch):
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, 256)
    traced = _kernel_on_cpu(monkeypatch)
    packed = eng.register_sparse("packed", ROWS, 8, init=init[:, :8])
    eng.register_sparse("wide", ROWS, 256, init=init)
    for g in grads:
        kv.wait(kv.push_sparse("wide", idx, g))
    assert not traced and eng.row_kernel_pushes == 0
    for g in grads:
        kv.wait(kv.push_sparse("packed", idx, g[..., :8]))
    assert set(traced) == {(packed.phys_rows, 128)}
    assert (eng.row_kernel_pushes, eng.packed_pushes) == (3, 3)
    for name, width in (("packed", 8), ("wide", 256)):
        np.testing.assert_allclose(
            np.asarray(eng.store_global_device(name)),
            _sum_reference(init[:, :width], idx,
                           [g[..., :width] for g in grads]),
            rtol=1e-5, atol=1e-5)
    # A group is one push: the kernel writes the tables that it takes, the
    # scatter the one it does not, and the push is counted once.
    del traced[:]
    a = eng.register_sparse("a", ROWS, 128, init=init[:, :128])
    b = eng.register_sparse("b", ROWS, 64, init=init[:, :64])
    eng.register_sparse("c", ROWS, 256, init=init)
    for g in grads:
        token = eng.push_group(["a", "b", "c"], [idx, idx, idx],
                               [g[..., :128], g[..., :64], g])
    token.block_until_ready()
    assert set(traced) == {(a.rows_per_shard, 128), (b.phys_rows, 128)}
    assert (eng.stateful_pushes, eng.row_kernel_pushes,
            eng.packed_pushes) == (0, 6, 6)
    for name, width in (("a", 128), ("b", 64), ("c", 256)):
        np.testing.assert_allclose(
            np.asarray(eng.store_global_device(name)),
            _sum_reference(init[:, :width], idx,
                           [g[..., :width] for g in grads]),
            rtol=1e-5, atol=1e-5)
    eng.push_group(["c"], [idx], [grads[0]]).block_until_ready()
    assert (eng.row_kernel_pushes, eng.packed_pushes) == (6, 6)


# -- lane-packed tables by physical row ------------------------------------------


def _mates(table, S, rows):
    """The logical rows that share a physical row with one of ``rows``
    (``rows`` among them): global row r lives on shard r % S at local row
    r // S, ``pack`` local rows to a physical one."""
    everyone = np.arange(table.num_rows)
    where = lambda r: (r % S) * table.phys_rows + (r // S) // table.pack
    return everyone[np.isin(where(everyone), where(np.asarray(rows)))]


def _packed_traffic(W, dim):
    """``_traffic`` and two rows more in every worker's batch: row ``W``,
    which on ``W`` shards is local row 1 of shard 0 and so the hottest
    row's mate in any lane-packed table, and row 58, whose mates 50.. no
    push touches."""
    idx, init, grads = _traffic(W, dim)
    idx[:, 4], idx[:, 5] = W, 58
    return idx, init, grads


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["single", "grouped"])
@pytest.mark.parametrize("handle", [None, HANDLE], ids=["sum", "row_adagrad"])
@pytest.mark.parametrize("dim", [8, 64, 128])
@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_a_push_by_physical_row_matches_the_reference_and_the_scatter(
        cluster, dim, handle, grouped, monkeypatch):
    """With the CPU named among the kernel's platforms both pushes of a
    lane-packed table (``dim`` 8: 16 rows to a physical row, 64: two) go
    through placement, the combine by physical row and ``row_add``
    (interpreted), as an unpacked table's (128) through the combine alone:
    the float64 reference (every gradient once, duplicates, row-mates of
    one physical row in one push, unowned slots dropped on four shards),
    an untouched row-mate bit-unchanged, and XLA's scatter to f32
    rounding; under the handle, bit for bit."""
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _packed_traffic(W, dim)
    other = 64 if dim == 128 else 128       # a group mixes packed and not
    init_o = init[:, :1].repeat(other, axis=1)
    grads_o = [g[..., :1].repeat(other, axis=2) for g in grads]
    names = ["emb", "other"] if grouped else ["emb"]
    data = {"emb": (dim, init, grads), "other": (other, init_o, grads_o)}

    def run(engine):
        for n in names:
            engine.register_sparse(n, ROWS, data[n][0], init=data[n][1])
        for step in range(len(grads)):
            if grouped:
                token = engine.push_group(
                    names, [idx] * 2, [data[n][2][step] for n in names],
                    handle=handle)
            else:
                token = engine.push("emb", idx, grads[step], handle)
        token.block_until_ready()

    twin = SparseEngine(eng.mesh, eng.axis)
    run(twin)                               # XLA's scatter: the CPU's own
    assert twin.row_kernel_pushes == 0
    traced = _kernel_on_cpu(monkeypatch)
    run(eng)
    tables = [eng.table(n) for n in names]
    assert set(traced) == {(t.phys_rows, 128) for t in tables}
    packed = any(t.pack != 1 for t in tables)
    assert (eng.row_kernel_pushes, eng.packed_pushes, eng.stateful_pushes) \
        == (3, 3 * packed, 3 * (handle is not None))
    touched = np.unique(idx)
    for n, table in zip(names, tables):
        width, init_n, grads_n = data[n]
        got = np.asarray(eng.store_global_device(n))
        scattered = np.asarray(twin.store_global_device(n))
        if handle is None:
            want = _sum_reference(init_n, idx, grads_n)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(got, scattered, rtol=1e-5, atol=1e-5)
        else:
            ref = RowAdagrad(init_n)
            for g in grads_n:
                ref.push(idx, g)
            assert _row_error(got, ref.table) < TOL
            # One logical row a lane, and zeros: the merge adds nothing.
            assert (got == scattered).all()
            assert (np.asarray(eng._acc[n]) == np.asarray(twin._acc[n])).all()
        # Rows no push touched keep their bits, those among them that
        # share a physical row with a touched one too.
        quiet = np.setdiff1d(np.arange(ROWS), touched)
        assert (got[quiet] == init_n[quiet]).all()
        if table.pack != 1:
            quiet_mates = np.intersect1d(quiet, _mates(table, W, touched))
            assert len(quiet_mates) >= 1 and W in _mates(table, W, [0])
        assert np.isfinite(got).all()


# -- the combine both pushes share ----------------------------------------------


def _zipf(rng, rows, n):
    p = 1.0 / np.arange(1, rows + 1) ** 0.99
    return rng.choice(rows, size=n, p=p / p.sum()).astype(np.int32)


def _combine_as_before(owned, local, all_g, R):
    """The combine as ``_adagrad_sparse`` spelled it before the sort
    carried its keys: ``argsort``, then ids, ownership and gradients
    gathered by its order."""
    import jax.numpy as jnp

    m = local.shape[0]
    order = jnp.argsort(local)
    sr = local[order]
    sg = jnp.where(owned[order][:, None], all_g[order], 0)
    first = jnp.concatenate([jnp.ones((1,), bool), sr[1:] != sr[:-1]])
    seg = jnp.cumsum(first) - 1
    G_seg = jnp.zeros((m, sg.shape[1]), sg.dtype).at[seg].add(sg)
    return G_seg, jnp.full((m,), R, jnp.int32).at[seg].set(
        sr.astype(jnp.int32))


@pytest.mark.parametrize("case, S, n", [
    ("all distinct", 1, 64),
    ("all one row", 1, 64),
    ("zipf duplicates", 1, 512),
    ("unowned slots on four shards", 4, 512),
    ("a batch that is no multiple of 8", 1, 45),
])
def test_combine_rows_is_numpys_unique_and_add_at(case, S, n):
    """``_combine_rows`` on every shard of an ``S``-shard mesh, against
    ``np.unique`` / ``np.add.at`` of the slots the shard owns: distinct
    rows ascending and first, each with the sum of its slots' gradients,
    the sentinel past them."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pslite_tpu.parallel.sparse import _combine_rows

    rng = np.random.default_rng(len(case))
    rows, dim = 4 * 97, 16
    R = -(-rows // S)
    idx = {"all distinct": lambda: rng.permutation(rows)[:n],
           "all one row": lambda: np.full(n, 7)}.get(
               case, lambda: _zipf(rng, rows, n))().astype(np.int32)
    g = rng.normal(size=(n, dim)).astype(np.float32)

    def body(idx, g):
        owned = (idx % S) == lax.axis_index("kv")
        local = jnp.where(owned, idx // S, R)
        return tuple(x[None] for x in (
            *_combine_rows(local, g, R), *_combine_as_before(owned, local,
                                                             g, R)))

    out = jax.jit(jax.shard_map(
        body, mesh=_mesh(S), in_specs=(P(), P()),
        out_specs=(P("kv"),) * 5, check_vma=False))(idx, g)
    G_seg, row_seg, valid, G_before, row_before = map(np.asarray, out)
    assert row_seg.dtype == np.int32 and valid.dtype == bool
    # Bit for bit what the argsort and the three gathers gave, in every
    # valid row (past them the gradients are not for use).
    assert (row_seg == row_before).all()
    assert (G_seg[valid] == G_before[valid]).all()
    distinct = 0
    for shard in range(S):
        mine = (idx % S) == shard
        want_rows = np.unique(idx[mine] // S)
        k = len(want_rows)
        distinct += k
        assert (row_seg[shard, :k] == want_rows).all()
        assert (row_seg[shard, k:] == R).all()
        assert valid[shard, :k].all() and not valid[shard, k:].any()
        want = np.zeros((R, dim), np.float64)
        np.add.at(want, idx[mine] // S, g[mine].astype(np.float64))
        np.testing.assert_allclose(G_seg[shard, :k], want[want_rows],
                                   rtol=1e-5, atol=1e-5)
    assert distinct == len(np.unique(idx))
    if case == "all one row":
        # Stable: equal rows keep the batch's order, so the one sum is
        # the batch's own left-to-right f32 sum, bit for bit.
        acc = np.zeros(dim, np.float32)
        for row in g:
            acc = acc + row
        assert (G_seg[0, 0] == acc).all()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_the_stateful_push_gathers_nothing_out_of_the_batchs_ids():
    """The sort brings the sorted ids back beside the permutation, and a
    slot is owned where its sorted id is a row: no 1-D gather whose
    operand is the batch's ``s32[m]`` ids or ``pred[m]`` ownership is left
    in ``_adagrad_sparse`` (on a v5e each was ~1 ms of a 12.5 ms step)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from pslite_tpu.parallel.sparse import _adagrad_sparse

    m, R, dim = 96, 61, 128

    def body(st, ac, ix, g, lr, eps):
        return _adagrad_sparse("kv", 1, R, 1, dim, st, ac, ix, g, lr, eps)

    jaxpr = jax.make_jaxpr(jax.shard_map(
        body, mesh=_mesh(1),
        in_specs=(P("kv", None), P("kv"), P("kv", None),
                  P("kv", None, None), P(), P()),
        out_specs=(P("kv", None), P("kv")), check_vma=False))(
        jnp.zeros((R, dim)), jnp.zeros((R,)), jnp.zeros((1, m), jnp.int32),
        jnp.zeros((1, m, dim)), jnp.float32(LR), jnp.float32(EPS))
    eqns = list(_eqns(jaxpr.jaxpr))
    # Two sorts: the batch's ids with their positions, the segments' ids.
    sorts = [e for e in eqns if e.primitive.name == "sort"]
    assert sorted(len(e.invars) for e in sorts) == [1, 2]
    assert not [e.primitive.name for e in eqns
                if e.primitive.name.startswith("scatter")
                and e.invars[0].aval.shape == (m,)]     # no 1-D scatter of ids
    gathers = [e.invars[0].aval for e in eqns if e.primitive.name == "gather"]
    # The gradients by the sort's order, and the accumulator's touched rows.
    assert sorted(a.shape for a in gathers) == [(R,), (m, dim)]
    assert not [a for a in gathers
                if a.shape == (m,) and a.dtype in (jnp.int32, jnp.bool_)]


def test_a_pair_is_bound_once_and_an_unknown_handle_fails_by_name(cluster):
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, 8)
    eng.register_sparse("emb", ROWS, 8, init=init)
    parsed = []
    real = SparseEngine._parse_handle
    try:
        SparseEngine._parse_handle = staticmethod(
            lambda h: parsed.append(h) or real(h))
        for g in grads:
            ts = kv.push_sparse("emb", idx, g, HANDLE)
        kv.wait(ts)
    finally:
        SparseEngine._parse_handle = staticmethod(real)
    assert parsed == [HANDLE]                   # not once a push
    record = eng._bound[("emb", HANDLE, idx.shape[1])]
    assert record.kind == "row_adagrad" and len(record.params) == 2
    assert [float(p) for p in record.params] == [np.float32(LR),
                                                 np.float32(EPS)]
    with pytest.raises(log.CheckError, match="row_adagrid:0.1"):
        kv.push_sparse("emb", idx, grads[0], "row_adagrid:0.1")
    # A new registration of the name drops its records; the next binds.
    eng.register_sparse("emb", ROWS, 8, init=init)
    assert not eng._bound
    kv.wait(kv.push_sparse("emb", idx, grads[0], HANDLE))
    assert ("emb", HANDLE, idx.shape[1]) in eng._bound


# -- counters and spans ---------------------------------------------------------


def _gauges(kv):
    return kv.po.metrics.snapshot()["gauges"]


def test_counters_of_a_stateful_push(cluster):
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, 8)
    eng.register_sparse("emb", ROWS, 8, init=init)
    clock = profiling.stage_clock()
    before = _gauges(kv)
    assert before["engine.sparse.push.stateful"] == 0
    assert before["engine.sparse.acc.bytes"] == 0
    created = clock.state_create_ns
    kv.wait(kv.push_sparse("emb", idx, grads[0]))            # the sum
    assert _gauges(kv)["engine.sparse.push.stateful"] == 0
    assert clock.state_create_ns == created
    kv.wait(kv.push_sparse("emb", idx, grads[0], HANDLE))
    once = clock.state_create_ns
    assert once > created        # the accumulator's creation is counted
    kv.wait(kv.push_sparse("emb", idx, grads[1], HANDLE))
    assert clock.state_create_ns == once                     # and once
    after = _gauges(kv)
    assert after["engine.sparse.push.stateful"] == 2
    # What the device holds: a shard's accumulators in whole 128s.
    kept = eng.table("emb").acc_rows
    assert after["engine.sparse.acc.bytes"] == 4 * kept * W
    assert after["engine.state_create.s"] > before["engine.state_create.s"]
    # Nothing to copy and no callback: no op went through the pool.
    counters = kv.po.metrics.snapshot()["counters"]
    assert counters.get("kv.complete.threaded", 0) == 0
    assert kv._engine_pool is None


def test_the_span_names_the_handles_kind(cluster, tmp_path):
    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, 8)
    eng.register_sparse("emb", ROWS, 8, init=init)
    kv.wait(kv.push_sparse("emb", idx, grads[0], HANDLE))     # compile
    kv.wait(kv.push_sparse("emb", idx, grads[0]))
    with profiling.device_trace(str(tmp_path)):
        under = kv.push_sparse("emb", idx, grads[1], HANDLE)
        plain = kv.push_sparse("emb", idx, grads[1])
        kv.wait(under)
        kv.wait(plain)
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    profile = jax.profiler.ProfileData.from_file(path)
    spans = {int(dict(ev.stats)["ts"]): dict(ev.stats)
             for plane in profile.planes for line in plane.lines
             for ev in line.events if ev.name == profiling.OP_SPAN}
    assert spans[under]["handle"] == "row_adagrad"
    assert spans[under]["name"] == spans[plain]["name"] == "emb"
    assert "handle" not in spans[plain]


def test_the_lowered_program_carries_the_scopes(cluster):
    import jax.numpy as jnp

    kv, eng = cluster
    W = eng.num_shards
    table = eng.register_sparse("emb", ROWS, 8)
    eng.ensure_acc("emb")
    prog = eng._sparse_program("push_row_adagrad", table, 4)
    # A program routed by owner takes the table's overflow count last.
    count = [eng._overflow_count("emb")] * eng._routed(4)
    text = prog.lower(
        eng._stores["emb"], eng._acc["emb"], jnp.zeros((W, 4), jnp.int32),
        jnp.zeros((W, 4, 8), jnp.float32), jnp.float32(LR), jnp.float32(EPS),
        *count).as_text(debug_info=True)
    for scope in ("ps.sparse.route", "ps.sparse.combine", "ps.update",
                  "ps.sparse.push.scatter_add"):
        assert scope in text, scope
    # The sort and the segment sum are the combine's, not the update's; the
    # exchange's own sorts (a worker's slots bucketed by owner) are its.
    sort_lines = [l for l in text.splitlines() if "sort" in l
                  and "ps." in l]
    assert sort_lines and all(
        "ps.sparse.combine" in l or "ps.sparse.route.ids" in l
        for l in sort_lines)
    assert any("ps.sparse.combine" in l for l in sort_lines)


# -- the accumulator as the engine keeps it: whole 128s a shard ---------------


def _pushed_engine(W, rows=1003, dim=128, pushes=2, seed=5):
    """An engine over ``W`` devices with a table of ``rows`` rows (no
    multiple of 128 a shard) pushed under the handle, and the traffic."""
    eng = SparseEngine(_mesh(W))
    rng = np.random.default_rng(seed)
    init = rng.normal(size=(rows, dim)).astype(np.float32)
    idx = rng.integers(0, rows, size=(W, 32)).astype(np.int32)
    idx[:, 0], idx[:, 1] = 0, rows - 1
    grads = [rng.normal(size=(W, 32, dim)).astype(np.float32)
             for _ in range(pushes)]
    eng.register_sparse("t", rows, dim, init=init)
    for g in grads:
        eng.push("t", idx, g, HANDLE).block_until_ready()
    return eng, init, idx, grads


def _tails_are_zero(eng, name="t"):
    t = eng.table(name)
    kept = np.asarray(eng._acc[name]).view(np.uint32)
    assert kept.shape == (eng.num_shards * t.acc_rows,)
    return not kept.reshape(eng.num_shards, -1)[:, t.rows_per_shard:].any()


@pytest.mark.parametrize("form", ["interleaved", "global rows"])
@pytest.mark.parametrize("on", ["host", "device"])
@pytest.mark.parametrize("W", [1, 4])
def test_an_accumulator_round_trips_through_acc_array_and_set_acc_array(
        W, on, form):
    """What ``acc_array`` (interleaved, ``[W * rows_per_shard]``: the logical
    rows alone) or ``acc_global_device`` (``[num_rows]``) gives, set into a
    fresh engine as a host or a device array, is the kept accumulator bit
    for bit, tails zero, and the next push steps the same."""
    eng, init, idx, grads = _pushed_engine(W)
    t = eng.table("t")
    global_rows = form == "global rows"
    snap = eng.acc_global_device("t") if global_rows else eng.acc_array("t")
    assert snap.shape == ((1003,) if global_rows
                          else (W * t.rows_per_shard,))
    assert t.acc_rows > t.rows_per_shard
    if on == "host":
        snap = np.asarray(snap)
    fresh = SparseEngine(_mesh(W))
    fresh.register_sparse("t", 1003, 128, init=np.asarray(
        eng.store_global_device("t")))
    fresh.set_acc_array("t", snap, global_rows=global_rows)
    assert (np.asarray(fresh._acc["t"]).view(np.uint32)
            == np.asarray(eng._acc["t"]).view(np.uint32)).all()
    assert _tails_are_zero(fresh) and _tails_are_zero(eng)
    for e in (eng, fresh):
        e.push("t", idx, grads[0], HANDLE).block_until_ready()
    assert (np.asarray(fresh._acc["t"]) == np.asarray(eng._acc["t"])).all()
    assert (fresh.store_array("t") == eng.store_array("t")).all()


@pytest.mark.parametrize("on", ["host", "device, sharded as the engine's"])
@pytest.mark.parametrize("W", [1, 4])
def test_an_interleaved_accumulator_of_the_parents_length_loads(W, on):
    """A checkpoint from before the kept tail holds ``[W * rows_per_shard]``
    interleaved, on the host (npz) or sharded over the mesh (orbax, same
    fleet): it loads, every logical accumulator where it was."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.sparse import _interleave_rows

    eng = SparseEngine(_mesh(W))
    t = eng.register_sparse("t", 1003, 128)
    want = (np.random.default_rng(W).normal(size=1003) ** 2).astype(
        np.float32)
    old = _interleave_rows(want, 1003, t.rows_per_shard, W, np.float32)
    assert old.shape == (W * t.rows_per_shard,) != (W * t.acc_rows,)
    if on != "host":
        old = jax.device_put(old, NamedSharding(eng.mesh, P("kv")))
    eng.set_acc_array("t", old)
    assert (np.asarray(eng.acc_global_device("t")) == want).all()
    assert (np.asarray(eng.acc_array("t")) == np.asarray(old)).all()
    assert _tails_are_zero(eng)
    # And a length that is neither is refused by name.
    with pytest.raises(log.CheckError, match="bad accumulator shape"):
        eng.set_acc_array("t", np.zeros(W * t.acc_rows, np.float32))


def test_a_reshard_keeps_every_logical_accumulators_bits():
    """1 -> 4 -> 1 devices: rows per shard 1,003 -> 251 -> 1,003, kept
    1,024 -> 256 -> 1,024; every logical accumulator's bits survive, the
    tails are zero on every mesh, and a push after the round trip steps as
    one on an engine that never moved."""
    eng, init, idx, grads = _pushed_engine(1)
    twin, *_ = _pushed_engine(1)
    want = np.asarray(eng.acc_global_device("t")).view(np.uint32)
    assert want.any()
    for W, kept in ((4, 256), (1, 1024)):
        eng.reshard(_mesh(W))
        assert eng.table("t").acc_rows == kept
        assert (np.asarray(eng.acc_global_device("t")).view(np.uint32)
                == want).all()
        assert _tails_are_zero(eng)
    for e in (eng, twin):
        e.push("t", idx, grads[0], HANDLE).block_until_ready()
    assert (np.asarray(eng._acc["t"]) == np.asarray(twin._acc["t"])).all()
    assert (eng.store_array("t") == twin.store_array("t")).all()
