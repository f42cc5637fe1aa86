"""A step's rows of MANY tables in one ``KVWorker`` call (PR 50):
``pull_sparse_group`` / ``push_sparse_group`` are ONE ``_engine_op`` each over
``SparseEngine.pull_group`` / ``push_group``: one timestamp, one ``ps.kv.op``
span, one ``KV_OP`` note, one launch, whatever the number of tables.

Held here: a grouped op leaves every store and hands back every pulled row
bit for bit as the same tables' one-table calls do (26 tables, one shard and
four, the plain sum and ``row_adagrad``); tables of 3, 4 and 10 rows beside
one of 100,003 under a batch of 2,048 (more slots than rows) through the
kernel the chip writes them with; ``outs=``, ``callback``, ``wait`` and
``get_pulled`` of a grouped pull (a ``PulledGroup``: PR 53); the refusal of
a table named twice; the group counter (``SPARSE_GROUP`` notes, gauges
``engine.sparse.group.ops`` / ``.tables``) and the span's ``tables``.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh  # noqa: E402

from pslite_tpu import KVWorker  # noqa: E402
from pslite_tpu.parallel.sparse import PulledGroup  # noqa: E402
from pslite_tpu.utils import profiling  # noqa: E402
from pslite_tpu.utils.logging import CheckError  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

DIM = 64
# The cell's shape in small: tables with fewer rows than a step has lookups
# for them, tables with more, one width, one batch size a table.
ROWS_26 = [3, 4, 10, 14, 36, 63, 108, 155, 976, 1543, 2208, 7120, 7420,
           401, 402, 403, 404, 405, 406, 407, 408, 409, 410, 411, 412, 413]
N_26 = 48


def _names(k):
    return [f"emb{i:02d}" for i in range(k)]


def _cluster(shards):
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.workers[0].van.set_mesh(
        Mesh(np.array(jax.devices()[:shards]), ("kv",)))
    c.start()
    kv = KVWorker(0, 0, postoffice=c.workers[0])
    return c, kv, kv.po.van.sparse_engine


@pytest.fixture()
def one_shard():
    c, kv, eng = _cluster(1)
    yield kv, eng
    c.finalize()


def _traffic(rows, W, n, seed):
    """Seeded ids and gradients a table: a row every worker asks for, a
    duplicate within a worker."""
    rng = np.random.default_rng(seed)
    idx = [rng.integers(0, r, size=(W, n)).astype(np.int32) for r in rows]
    for i in idx:
        i[:, 0] = 0
        i[:, 1] = i[:, 2]
    grads = [rng.normal(size=(W, n, DIM)).astype(np.float32) for _ in rows]
    return idx, grads


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("handle", [None, "row_adagrad:0.05,1e-8"],
                         ids=["sum", "row_adagrad"])
@pytest.mark.parametrize("shards, k", [(1, 26), (4, 8)],
                         ids=["one-shard-26-tables", "four-shards-8-tables"])
def test_a_grouped_op_is_its_tables_one_table_ops_bit_for_bit(shards, k,
                                                              handle):
    """``k`` tables pushed twice and pulled, once through the grouped calls
    and once through ``k`` one-table calls each, on the same seeded ids and
    gradients: the stores, the accumulators and the pulled rows are equal
    bit for bit.  (All 26 on one shard, as the cell runs them; the first 8,
    of 3 to 155 rows, over four, where every program is compiled for four
    devices and the 26 would take a minute of tier-1.)"""
    names, table_rows = _names(k), ROWS_26[:k]
    idx, grads = _traffic(table_rows, shards, N_26, seed=50)
    c, kv, eng = _cluster(shards)
    try:
        solo = [n + ".solo" for n in names]
        for n, s, r in zip(names, solo, table_rows):
            eng.register_sparse(n, r, DIM)
            eng.register_sparse(s, r, DIM)
        for _ in range(2):
            kv.wait(kv.push_sparse_group(names, idx, grads, handle))
            for s, i, g in zip(solo, idx, grads):
                kv.wait(kv.push_sparse(s, i, g, handle))
        ts = kv.pull_sparse_group(names, idx)
        kv.wait(ts)
        pulled = kv.get_pulled(ts)
        # One result for the one class of (DIM, f32), an entry a table.
        assert type(pulled) is PulledGroup and len(pulled) == k
        assert [a.shape for a in pulled.arrays] == [(shards, k * N_26, DIM)]
        for n, s, i, rows in zip(names, solo, idx, pulled):
            one = kv.pull_sparse(s, i)
            kv.wait(one)
            assert rows.shape == (shards, N_26, DIM)
            assert (_bits(rows) == _bits(kv.get_pulled(one))).all(), n
            assert (_bits(eng.store_raw(n)) == _bits(eng.store_raw(s))).all()
            if handle is not None:
                assert (_bits(eng._acc[n]) == _bits(eng._acc[s])).all(), n
        # Something was written, and to the hot row of every table.
        assert all(np.abs(np.asarray(p)[:, 0]).max() > 0 for p in pulled)
    finally:
        c.finalize()


@pytest.fixture()
def kernel_on_cpu(monkeypatch):
    """The table written by ``ops/row_add.py``, interpreted, as the chip
    writes it (``benchmark/tests/test_rehearsal_packed.py``)."""
    from pslite_tpu.parallel import sparse

    monkeypatch.setitem(sparse._ROW_ADD_INTERPRET, "cpu", True)


def test_tables_smaller_than_their_batch_through_the_kernel(one_shard,
                                                            kernel_on_cpu):
    """Tables of 3, 4 and 10 rows (2, 2 and 5 physical rows of two
    row-mates) beside one of 100,003, each sent 2,048 slots: two grouped
    pushes and a grouped pull against float64, every row of the small
    tables read back."""
    kv, eng = one_shard
    rows, n = [3, 4, 10, 100_003], 2048
    names = _names(4)
    for name, r in zip(names, rows):
        table = eng.register_sparse(name, r, DIM)
        assert table.pack == 2 and eng._row_kernel(table)
    idx, grads = _traffic(rows, 1, n, seed=51)
    for _ in range(2):
        kv.wait(kv.push_sparse_group(names, idx, grads))
    assert eng.row_kernel_pushes == 2 and eng.packed_pushes == 2
    ts = kv.pull_sparse_group(names, idx)
    kv.wait(ts)
    for r, i, g, got in zip(rows, idx, grads, kv.get_pulled(ts)):
        want = np.zeros((r, DIM))
        np.add.at(want, i[0], 2 * g[0].astype(np.float64))
        if r <= n:
            assert set(i[0]) == set(range(r))      # every row it has
        scale = np.abs(want[i[0]]).max(axis=1, keepdims=True)
        err = np.abs(np.asarray(got)[0] - want[i[0]]) / np.maximum(scale, 1)
        assert err.max() < 2e-4, (r, err.max())


def test_outs_callback_and_wait_of_a_grouped_op(one_shard):
    kv, eng = one_shard
    rows = [5, 300, 4001]
    names = _names(3)
    inits = [np.random.default_rng(7 + r).normal(size=(r, DIM)).astype(
        np.float32) for r in rows]
    for name, r, init in zip(names, rows, inits):
        eng.register_sparse(name, r, DIM, init=init)
    idx, grads = _traffic(rows, 1, 96, seed=52)
    # ``outs=``: each table's rows in its own host buffer, on the
    # completion thread; the callback fires once for the whole op.
    outs = [np.full((1, 96, DIM), np.nan, np.float32) for _ in rows]
    fired = []
    ts = kv.pull_sparse_group(names, idx, outs=outs,
                              callback=lambda: fired.append(1))
    kv.wait(ts)
    assert fired == [1]
    for init, i, out, dev in zip(inits, idx, outs, kv.get_pulled(ts)):
        assert (_bits(out) == _bits(init[i])).all()
        assert (_bits(dev) == _bits(out)).all()
    with pytest.raises(CheckError, match="one host buffer a table"):
        kv.pull_sparse_group(names, idx, outs=outs[:2])
    # A grouped push waited for and followed at once by a second: the
    # stores the first returned are donated to the second.
    pushed = []
    first = kv.push_sparse_group(names, idx, grads,
                                 callback=lambda: pushed.append(1))
    kv.wait(first)
    second = kv.push_sparse_group(names, idx, grads)
    assert second != first and pushed == [1]
    kv.wait(second)
    kv.wait(first)                                # a later wait: at once
    ts = kv.pull_sparse_group(names, idx)
    kv.wait(ts)
    for init, i, g, got in zip(inits, idx, grads, kv.get_pulled(ts)):
        want = init.astype(np.float64)
        np.add.at(want, i[0], 2 * g[0].astype(np.float64))
        assert np.allclose(np.asarray(got)[0], want[i[0]], atol=1e-4)


def test_a_table_named_twice_in_a_grouped_push_is_refused_by_name(one_shard):
    kv, eng = one_shard
    for name in ("users", "items"):
        eng.register_sparse(name, 50, DIM)
    idx, grads = _traffic([50, 50, 50], 1, 8, seed=53)
    with pytest.raises(CheckError, match=r"\['items'\] appear twice"):
        kv.push_sparse_group(["items", "users", "items"], idx, grads)
    # The same table twice in a grouped PULL reads it twice: nothing is
    # donated.
    ts = kv.pull_sparse_group(["items", "items"], idx[:2])
    kv.wait(ts)
    assert len(kv.get_pulled(ts)) == 2


def test_get_pulled_keeps_lists_and_trims_them_by_their_bytes(one_shard,
                                                              monkeypatch):
    kv, eng = one_shard
    names = _names(3)
    for name in names:
        eng.register_sparse(name, 100, DIM)
    idx, _ = _traffic([100] * 3, 1, 32, seed=54)
    a_list = 3 * 32 * DIM * 4
    stamps = []
    for _ in range(10):
        ts = kv.pull_sparse_group(names, idx)
        kv.wait(ts)
        stamps.append(ts)
    # The window of the last 8 results, a sequence an entry.
    kept = [ts for ts in stamps if kv.get_pulled(ts) is not None]
    assert kept == stamps[-8:]
    last = kv.get_pulled(stamps[-1])
    assert type(last) is PulledGroup
    assert sum(a.nbytes for a in last.arrays) == a_list
    # While a heavy bucket is registered the window is held to a budget of
    # bytes, and a sequence weighs what its class arrays weigh: room for two
    # and a half keeps two.
    monkeypatch.setattr(kv, "_results_heavy", True)
    monkeypatch.setattr(kv, "_DEVICE_RESULTS_BYTES", int(2.5 * a_list))
    ts = kv.pull_sparse_group(names, idx)
    kv.wait(ts)
    assert [s for s in stamps + [ts] if kv.get_pulled(s) is not None] \
        == [stamps[-1], ts]


def test_one_note_one_span_and_the_group_counter_an_op(monkeypatch):
    """A grouped op notes what a one-table op notes (one ``ENGINE_OP``, one
    ``KV_OP``, one ``SPARSE_ROUTE``, one ``COMPLETED``) and one
    ``SPARSE_GROUP`` with its tables; a one-table op notes no group."""
    clock = profiling.StageClock()
    monkeypatch.setattr(profiling, "_clock", clock)
    c, kv, eng = _cluster(1)
    try:
        names = _names(5)
        for name in names:
            eng.register_sparse(name, 40, DIM)
        idx, grads = _traffic([40] * 5, 1, 16, seed=55)
        kv.wait(kv.push_sparse_group(names, idx, grads))      # builds
        kv.wait(kv.pull_sparse_group(names, idx))
        kv.wait(kv.push_sparse(names[0], idx[0], grads[0]))
        clock.fold()
        notes = []
        monkeypatch.setattr(clock, "note", notes.append)
        monkeypatch.setattr(eng, "_note", notes.append)
        monkeypatch.setattr(kv, "_note", notes.append)
        spans = []

        class Span:
            def __init__(self, span, **kw):
                self.name, self.meta = span, dict(kw)
                spans.append(self)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **kw):
                self.meta.update(kw)

        from pslite_tpu.kv import kv_app

        monkeypatch.setattr(kv_app, "TraceAnnotation", Span)
        monkeypatch.setattr(kv_app, "tracing", lambda: True)
        ts_push = kv.push_sparse_group(names, idx, grads,
                                       "row_adagrad:0.1,1e-8")
        ts_pull = kv.pull_sparse_group(names[:3], idx[:3])
        kv.wait(ts_push)
        kv.wait(ts_pull)
        kinds = [note[0] for note in notes]
        for kind in (profiling.KV_OP, profiling.ENGINE_OP,
                     profiling.SPARSE_ROUTE, profiling.SPARSE_GROUP,
                     profiling.COMPLETED):
            assert kinds.count(kind) == 2, (kind, kinds)
        assert [note[2] for note in notes
                if note[0] == profiling.SPARSE_GROUP] == [5, 3]
        # The group's note comes before its op's ENGINE_OP (the launch's
        # parts between them), whose KV_OP is the note next after it
        # (``StageClock._route_of``).
        for i, kind in enumerate(kinds):
            if kind == profiling.ENGINE_OP:
                assert kinds[i - 2:i] == [profiling.SPARSE_GROUP,
                                          profiling.LAUNCH]
                assert kinds[i + 1] == profiling.KV_OP
        ops = [s for s in spans if s.name == profiling.OP_SPAN]
        assert [(s.meta["ts"], s.meta["name"], s.meta["tables"])
                for s in ops] == [(ts_push, "emb00", 5), (ts_pull, "emb00", 3)]
        # A grouped op is its kind.
        assert [s.meta["op"] for s in ops] == ["sparse.push", "sparse.pull"]
        assert ops[0].meta["handle"] == "row_adagrad"
        assert "handle" not in ops[1].meta
        waits = [s for s in spans if s.name == profiling.COMPLETE_SPANS[0]]
        assert [s.meta["ts"] for s in waits] == [ts_push, ts_pull]
        # A one-table op's span carries no ``tables`` and notes no group.
        del notes[:], spans[:]
        kv.wait(kv.push_sparse(names[0], idx[0], grads[0]))
        assert profiling.SPARSE_GROUP not in [note[0] for note in notes]
        assert "tables" not in spans[0].meta
    finally:
        c.finalize()


def test_the_group_counter_reads_as_gauges_and_over_a_window():
    """``engine.sparse.group.ops`` / ``.tables`` in a node's registry, and
    ``StageClock.grouped`` over the clock's slots, as ``routed`` reads."""
    from pslite_tpu.telemetry.metrics import Registry
    from pslite_tpu.utils.profiling import (ENGINE_OP, SPARSE_GROUP,
                                            SPARSE_ROUTE, StageClock)

    clock = StageClock()
    slot = 1 << StageClock.SLOT_SHIFT
    t = 100 * slot
    for k in range(6):                      # an op a slot, 26 and 2 tables
        end = t + k * slot + 1000
        clock.note((SPARSE_ROUTE, end, 53248, -1, -1))
        clock.note((SPARSE_GROUP, end, 26 if k % 2 else 2, -1, -1))
        clock.note((ENGINE_OP, end, 10, 20, 30))
    clock.note((SPARSE_ROUTE, t + 6 * slot + 5, 64, -1, -1))   # a lone op
    clock.note((ENGINE_OP, t + 6 * slot + 5, 10, 20, 30))
    assert clock.grouped_totals() == (3 * 26 + 3 * 2, 6)
    assert clock.routed_totals() == (6 * 53248 + 64, 7)
    (tables, ops), whole, seconds = clock.grouped(
        (t + slot) / 1e9, (t + 5 * slot + 10) / 1e9)
    assert (tables, ops, whole) == (26 + 2 + 26 + 2, 4, 4)
    assert seconds == pytest.approx(4 * slot / 1e9)
    assert clock.grouped(0.0, 1.0) == ((0, 0), 0, 0.0)
    assert clock.totals()["launch"] == (7 * 30, 7)

    from pslite_tpu.parallel.sparse import SparseEngine

    eng = SparseEngine(Mesh(np.array(jax.devices()[:1]), ("kv",)))
    eng._clock = clock
    registry = Registry()
    eng.export(registry)
    gauges = registry.snapshot()["gauges"]
    assert gauges["engine.sparse.group.ops"] == 6
    assert gauges["engine.sparse.group.tables"] == 84


@pytest.mark.parametrize("handle", [None, "row_adagrad:0.05,1e-8"],
                         ids=["sum", "row_adagrad"])
@pytest.mark.parametrize("shards", [1, 4], ids=["one-shard", "four-shards"])
def test_a_grouped_pooled_op_is_its_tables_one_table_pooled_ops(shards,
                                                                handle):
    """``pool="sum"`` in the grouped calls (ISSUE 54): every table with a bag
    size of its own in ONE op.  Six tables with bags of 1 to 27 ids pushed
    twice and pulled, once through the grouped calls and once through six
    one-table pooled calls: stores, accumulators and pooled rows bit for
    bit; the grouped pull's one result holds a table's ``B`` pooled rows side
    by side, ``[W, 6 * B, d]``, whatever the bags held."""
    rows, bags, B = ROWS_26[:6], [8, 1, 3, 2, 27, 5], 16
    names = _names(6)
    rng = np.random.default_rng(54)
    idx = [rng.integers(0, r, size=(shards, B, h)).astype(np.int32)
           for r, h in zip(rows, bags)]
    grads = [rng.normal(size=(shards, B, DIM)).astype(np.float32)
             for _ in rows]
    c, kv, eng = _cluster(shards)
    try:
        solo = [n + ".solo" for n in names]
        for n, s, r in zip(names, solo, rows):
            eng.register_sparse(n, r, DIM)
            eng.register_sparse(s, r, DIM)
        for _ in range(2):
            kv.wait(kv.push_sparse_group(names, idx, grads, handle,
                                         pool="sum"))
            for s, i, g in zip(solo, idx, grads):
                kv.wait(kv.push_sparse(s, i, g, handle, pool="sum"))
        ts = kv.pull_sparse_group(names, idx, pool="sum")
        kv.wait(ts)
        pulled = kv.get_pulled(ts)
        assert type(pulled) is PulledGroup and len(pulled) == 6
        assert [a.shape for a in pulled.arrays] == [(shards, 6 * B, DIM)]
        for n, s, i, got in zip(names, solo, idx, pulled):
            one = kv.pull_sparse(s, i, pool="sum")
            kv.wait(one)
            assert got.shape == (shards, B, DIM)
            assert (_bits(got) == _bits(kv.get_pulled(one))).all(), n
            assert (_bits(eng.store_raw(n)) == _bits(eng.store_raw(s))).all()
            if handle is not None:
                assert (_bits(eng._acc[n]) == _bits(eng._acc[s])).all(), n
        assert all(np.abs(np.asarray(p)).max() > 0 for p in pulled)
        # The group's record is keyed by the bags: (B, h) a table, B where
        # a bag is one id.
        key = ("pull", tuple(names), None,
               tuple(B if h == 1 else (B, h) for h in bags))
        assert key in eng._bound
        assert eng._bound[key].pooled == (shards * B * 6,
                                          shards * B * sum(bags))
    finally:
        c.finalize()
