"""A lookup that is a BAG (ISSUE 54): ``pool="sum"`` on the four sparse calls
of ``KVWorker``.  Ids ``[W, B, h]``, a worker's ``B`` bags of ``h`` ids; a
pooled pull gives ``[W, B, d]``, the sum of a bag's rows; a pooled push takes
``[W, B, d]``, ONE gradient a bag, which every slot of the bag brings to its
row.

Held here, on CPU devices at small sizes:

- the two equivalences that define the pooled forms: the pooled pull is the
  unpooled pull of ``[W, B * h]`` summed over a bag, and the pooled push leaves
  the store and the accumulator the unpooled push of the gradient repeated
  ``h`` times leaves, bit for bit (the same values are added in the same
  order); single and grouped, the plain sum and ``row_adagrad``, ``pack`` 1
  and 2, one shard and four, ``W > 1`` with a row every worker names, an id
  repeated inside a bag, whole bags repeated, a table smaller than a bag;
- ``h = 1`` is the call without ``pool``: the same program key, the same
  record, no new compile, the same bits;
- agreement with the plain reference ``benchmark/bags_reference.py`` (numpy,
  float64) on seeded tables;
- what is refused, by name; the three counters and the span's ``pool``.
"""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pslite_tpu import KVWorker  # noqa: E402
from pslite_tpu.parallel import sparse  # noqa: E402
from pslite_tpu.parallel.sparse import PulledGroup  # noqa: E402
from pslite_tpu.utils import profiling  # noqa: E402
from pslite_tpu.utils.logging import CheckError  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
HANDLE = "row_adagrad:0.05,1e-8"
# The deployment in small: bags of 1 (a row), a few and many ids; a table
# with fewer rows than a bag has slots; one no multiple of the shards.
ROWS = [3, 40, 1003, 257]
BAGS = [8, 1, 3, 27]
B = 12


def _cluster(shards):
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.workers[0].van.set_mesh(
        Mesh(np.array(jax.devices()[:shards]), ("kv",)))
    c.start()
    kv = KVWorker(0, 0, postoffice=c.workers[0])
    return c, kv, kv.po.van.sparse_engine


@pytest.fixture(params=[1, 4], ids=["one-shard", "four-shards"])
def cluster(request):
    c, kv, eng = _cluster(request.param)
    yield kv, eng, request.param
    c.finalize()


@pytest.fixture()
def one_shard():
    c, kv, eng = _cluster(1)
    yield kv, eng
    c.finalize()


@pytest.fixture()
def bags_reference(monkeypatch):
    """The benchmark's modules import each other by bare name."""
    monkeypatch.syspath_prepend(BENCH)
    import bags_reference

    yield bags_reference
    for name, module in list(sys.modules.items()):
        if (getattr(module, "__file__", None) or "").startswith(BENCH):
            del sys.modules[name]


def _traffic(rows, bags, W, dim, seed):
    """Seeded bags and bag gradients a table: row 0 in every worker's first
    bag, an id repeated inside a bag, a whole bag repeated."""
    rng = np.random.default_rng(seed)
    idx, grads = [], []
    for r, h in zip(rows, bags):
        i = rng.integers(0, r, size=(W, B, h)).astype(np.int32)
        i[:, 0, 0] = 0                  # a row every worker names
        if h > 1:
            i[:, 1, 1] = i[:, 1, 0]     # an id twice in one bag
        i[:, 3] = i[:, 2]               # a bag brought twice
        idx.append(i)
        grads.append(rng.normal(size=(W, B, dim)).astype(np.float32))
    return idx, grads


def _out(idx, grads):
    """The bags multiplied out: ``[W, B * h]`` ids and each gradient
    repeated ``h`` times, slot for slot."""
    return ([i.reshape(i.shape[0], -1) for i in idx],
            [np.repeat(g, i.shape[2], axis=1) for i, g in zip(idx, grads)])


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _names(tag, k=len(ROWS)):
    return [f"{tag}{i}" for i in range(k)]


def _register(eng, tag, dim, seed=3):
    names = _names(tag)
    for k, (n, r) in enumerate(zip(names, ROWS)):
        init = np.random.default_rng(seed + k).normal(
            size=(r, dim)).astype(np.float32)
        eng.register_sparse(n, r, dim, init=init)
    return names


# A pooled sum of at most 27 f32 rows against the f32 sum of the same rows
# in another order: each of h - 1 additions rounds to half an ulp of a
# partial sum that is at most the sum of the rows' magnitudes.
def _sum_tolerance(rows, h):
    return h * np.finfo(np.float32).eps * np.abs(rows).sum(axis=2)


@pytest.mark.parametrize("group", [False, True], ids=["single", "group"])
@pytest.mark.parametrize("dim", [128, 64], ids=["pack-1", "pack-2"])
def test_the_pooled_pull_is_the_unpooled_pull_summed_over_a_bag(cluster, dim,
                                                                group):
    kv, eng, W = cluster
    names = _register(eng, "t", dim)
    assert eng.table(names[0]).pack == 128 // dim
    idx, _ = _traffic(ROWS, BAGS, W, dim, seed=11)
    flat, _ = _out(idx, idx)
    if group:
        ts = kv.pull_sparse_group(names, idx, pool="sum")
        kv.wait(ts)
        pulled = kv.get_pulled(ts)
        assert type(pulled) is PulledGroup
        # One result for the one class, a table's B pooled rows side by side.
        assert [a.shape for a in pulled.arrays] == [(W, len(ROWS) * B, dim)]
        assert pulled.entries == tuple((0, k * B, B)
                                       for k in range(len(ROWS)))
        pooled = [np.asarray(p) for p in pulled]
    else:
        pooled = []
        for n, i in zip(names, idx):
            ts = kv.pull_sparse(n, i, pool="sum")
            kv.wait(ts)
            pooled.append(np.asarray(kv.get_pulled(ts)))
    for n, i, f, h, got in zip(names, idx, flat, BAGS, pooled):
        ts = kv.pull_sparse(n, f)
        kv.wait(ts)
        rows = np.asarray(kv.get_pulled(ts)).reshape(W, B, h, dim)
        assert got.shape == (W, B, dim) and got.dtype == np.float32
        want = rows.astype(np.float64).sum(axis=2)
        assert (np.abs(got - want) <= _sum_tolerance(rows, h)).all(), n
        if h == 1:  # a bag of one is its row, bit for bit
            assert (_bits(got) == _bits(rows[:, :, 0])).all()
        # The id that lies twice in bag 1 was added twice: the bag's sum is
        # its distinct slots' and that row once more.
        if h == 2:
            assert np.allclose(got[:, 1], 2 * rows[:, 1, 0], rtol=1e-6)


@pytest.mark.parametrize("group", [False, True], ids=["single", "group"])
@pytest.mark.parametrize("handle", [None, HANDLE], ids=["sum", "row_adagrad"])
@pytest.mark.parametrize("dim", [128, 64], ids=["pack-1", "pack-2"])
def test_the_pooled_push_is_the_unpooled_push_of_the_repeated_gradient(
        cluster, dim, handle, group):
    """Two pushes each way on the same seeded tables: the stores and the
    accumulators are equal bit for bit (the pooled body adds the values the
    unpooled one adds, in its order: a slot's gradient is read through its
    bag where the unpooled body reads the repeated row)."""
    kv, eng, W = cluster
    pooled, plain = _register(eng, "p", dim), _register(eng, "u", dim)
    idx, grads = _traffic(ROWS, BAGS, W, dim, seed=12)
    flat, repeated = _out(idx, grads)
    for _ in range(2):
        if group:
            kv.wait(kv.push_sparse_group(pooled, idx, grads, handle,
                                         pool="sum"))
            kv.wait(kv.push_sparse_group(plain, flat, repeated, handle))
        else:
            for a, b, i, g, f, r in zip(pooled, plain, idx, grads, flat,
                                        repeated):
                kv.wait(kv.push_sparse(a, i, g, handle, pool="sum"))
                kv.wait(kv.push_sparse(b, f, r, handle))
    for a, b, r in zip(pooled, plain, ROWS):
        assert (_bits(eng.store_raw(a)) == _bits(eng.store_raw(b))).all(), a
        if handle is not None:
            assert (_bits(eng._acc[a]) == _bits(eng._acc[b])).all(), a
            assert np.asarray(eng._acc[a]).max() > 0
    # Something was written, and a table smaller than a bag has every row
    # touched.
    init = np.random.default_rng(3).normal(size=(ROWS[0], dim))
    got = np.asarray(eng.store_array(pooled[0]))
    got = got.reshape(W, -1, dim).transpose(1, 0, 2).reshape(-1, dim)
    assert (np.abs(got[:ROWS[0]] - init) > 1e-3).any(axis=1).all()


@pytest.fixture()
def kernels_on_cpu(monkeypatch):
    """The combine summed by ``ops/segment_sum.py``, the accumulator updated
    by ``ops/acc_update.py`` and the table written by ``ops/row_add.py``,
    interpreted, as the chip does all three."""
    monkeypatch.setitem(sparse._ROW_ADD_INTERPRET, "cpu", True)
    monkeypatch.setitem(sparse._SEGMENT_SUM_INTERPRET, "cpu", True)
    monkeypatch.setitem(sparse._ACC_UPDATE_INTERPRET, "cpu", True)


@pytest.mark.parametrize("handle", [None, HANDLE], ids=["sum", "row_adagrad"])
def test_the_pooled_push_through_the_kernels_the_chip_runs(
        one_shard, kernels_on_cpu, handle, bags_reference):
    """The chip's bodies (the combine that reads a slot's gradient through its
    bag, then the kernels) in one grouped push of two tables whose
    accumulators take ``acc_update``'s pass, one of 256 rows and one of 1,003,
    no multiple of 128 (kept in whole 128s: 1,024): equal to the unpooled
    push bit for bit, and to float64.  Which body an accumulator takes is a
    cost's verdict: a smaller batch into the same table keeps XLA's pair."""
    kv, eng = one_shard
    rows, bags, dim = [256, 1003], [27, 3], 128
    names, twins = ["a", "b"], ["a.u", "b.u"]
    for n, t, r in zip(names, twins, rows):
        eng.register_sparse(n, r, dim)
        eng.register_sparse(t, r, dim)
    rng = np.random.default_rng(21)
    big = 20                    # 540 slots: enough for the pass to pay
    idx = [rng.integers(0, r, size=(1, big, h)).astype(np.int32)
           for r, h in zip(rows, bags)]
    idx[0][0, 1, 1] = idx[0][0, 1, 0]
    grads = [rng.normal(size=(1, big, dim)).astype(np.float32) for _ in rows]
    flat, repeated = _out(idx, grads)
    for _ in range(2):
        kv.wait(kv.push_sparse_group(names, idx, grads, handle, pool="sum"))
        kv.wait(kv.push_sparse_group(twins, flat, repeated, handle))
    assert eng.row_kernel_pushes == 4 and eng.segsum_kernel_pushes == 4
    if handle is not None:
        assert eng.acc_kernel_pushes == 4
        assert eng._acc_kernel(eng.table("a"), (big, 27))
        # The rule is the cost alone: 60 slots pay for the one grid step
        # over 1,024 accumulators, 45 do not; no row count is refused.
        assert eng.table("b").acc_rows == 1024
        assert eng._acc_kernel(eng.table("b"), (big, 3))
        assert not eng._acc_kernel(eng.table("b"), (big - 5, 3))
        assert eng.acc_kernel_tables == 8
    for n, t, r, i, g in zip(names, twins, rows, idx, grads):
        assert (_bits(eng.store_raw(n)) == _bits(eng.store_raw(t))).all(), n
        ref = bags_reference.bag_reference(np.arange(r), dim, handle)
        c = ref.contribution(i, g)
        ref.push(c)
        ref.push(c)
        got = np.asarray(eng.store_array(n))[:r]
        scale = np.maximum(np.abs(ref.sums).max(axis=1), 0.05)
        assert (np.abs(got - ref.sums).max(axis=1) / scale).max() < 2e-5, n
        if handle is not None:
            assert (_bits(eng._acc[n]) == _bits(eng._acc[t])).all(), n
            assert np.allclose(np.asarray(eng._acc[n])[:r], ref.acc,
                               rtol=2e-5, atol=0)


@pytest.mark.parametrize("handle", [None, HANDLE], ids=["sum", "row_adagrad"])
def test_the_system_agrees_with_the_plain_reference(cluster, handle,
                                                    bags_reference):
    """``benchmark/bags_reference.py`` (numpy, float64, nothing of the
    program) follows three pooled pushes of seeded bags into seeded tables;
    the pooled pull after them, the tables and the accumulators agree.  f32
    sums of at most a few dozen values a row against float64: 2e-5 of a
    row's largest value (the four-servers test's tolerance)."""
    kv, eng, W = cluster
    dim = 128
    names = _names("t")
    for n, r in zip(names, ROWS):
        eng.register_sparse(n, r, dim)
    steps = [_traffic(ROWS, BAGS, W, dim, seed=30 + s) for s in range(3)]
    refs = [bags_reference.bag_reference(np.arange(r), dim, handle)
            for r in ROWS]
    for idx, grads in steps:
        kv.wait(kv.push_sparse_group(names, idx, grads, handle, pool="sum"))
        for ref, i, g in zip(refs, idx, grads):
            c = ref.contribution(i, g)
            # The vectorised G is the plain loop's, and the bag multiplied
            # out slot by slot through the one-row reference's.
            loop = bags_reference.contribution_by_loop(ref.rows, i, g)
            assert np.allclose(c.dense(), loop, rtol=1e-12, atol=1e-12)
            assert np.allclose(loop, bags_reference.RowSumReference(
                ref.rows, dim).contribution(
                    *bags_reference.multiplied_out(i, g)),
                rtol=1e-12, atol=1e-12)
            assert (c.at == np.flatnonzero(np.isin(ref.rows, i))).all()
            ref.push(c)
    idx = steps[0][0]
    ts = kv.pull_sparse_group(names, idx, pool="sum")
    kv.wait(ts)
    for n, r, h, ref, i, got in zip(names, ROWS, BAGS, refs, idx,
                                    kv.get_pulled(ts)):
        want = ref.pull_pooled(i)
        scale = np.maximum(np.abs(want).max(axis=-1), 0.05)
        err = np.abs(np.asarray(got) - want).max(axis=-1) / scale
        assert err.max() < 2e-5 * h, (n, err.max())
        table = np.asarray(eng.store_array(n))
        rps = eng.table(n).rows_per_shard
        table = table.reshape(W, rps, dim).transpose(1, 0, 2).reshape(
            -1, dim)[:r]
        scale = np.maximum(np.abs(ref.sums).max(axis=1), 0.05)
        assert (np.abs(table - ref.sums).max(axis=1) / scale).max() < 2e-5
        if handle is not None:
            acc = np.asarray(eng.acc_global_device(n))
            assert np.allclose(acc, ref.acc, rtol=2e-5, atol=0), n


def test_bags_of_one_id_take_the_program_of_the_call_without_pool(one_shard):
    """``h = 1`` IS the path without ``pool``: the same program key, the same
    bound record, no new compile, the same bits, single and grouped, pull and
    push."""
    kv, eng = one_shard
    dim, W = 128, 1
    names = _names("t", 2)
    for n in names:
        eng.register_sparse(n, 50, dim)
    rng = np.random.default_rng(5)
    idx = [rng.integers(0, 50, size=(W, B)).astype(np.int32) for _ in names]
    grads = [rng.normal(size=(W, B, dim)).astype(np.float32) for _ in names]
    kv.wait(kv.push_sparse_group(names, idx, grads, HANDLE))
    kv.wait(kv.push_sparse(names[0], idx[0], grads[0]))
    ts = kv.pull_sparse_group(names, idx)
    kv.wait(ts)
    plain = [np.asarray(p) for p in kv.get_pulled(ts)]
    kv.wait(kv.pull_sparse(names[0], idx[0]))
    programs, bound = dict(eng._programs), dict(eng._bound)
    built = profiling.stage_clock().programs_built
    compiled = {k: p._cache_size() for k, p in programs.items()}

    bags = [i[:, :, None] for i in idx]                     # [W, B, 1]
    kv.wait(kv.push_sparse_group(names, bags, grads, HANDLE, pool="sum"))
    kv.wait(kv.push_sparse(names[0], bags[0], grads[0], pool="sum"))
    ts = kv.pull_sparse_group(names, bags, pool="sum")
    kv.wait(ts)
    pooled = [np.asarray(p) for p in kv.get_pulled(ts)]
    one = kv.pull_sparse(names[0], bags[0], pool="sum")
    kv.wait(one)
    assert eng._programs == programs and eng._bound == bound
    assert profiling.stage_clock().programs_built == built
    assert {k: p._cache_size() for k, p in eng._programs.items()} == compiled
    assert kv.get_pulled(one).shape == (W, B, dim)
    # The second pushes moved the rows; pulled again without ``pool`` they
    # are what the pooled pull gave.
    ts = kv.pull_sparse_group(names, idx)
    kv.wait(ts)
    for a, b, before in zip(pooled, kv.get_pulled(ts), plain):
        assert (_bits(a) == _bits(b)).all()
        assert (a != before).any()
    # Bags of one that lie on the device go to the same program as they lie
    # (no reshape, which would be a launch): the same key and record.
    on_device = jax.device_put(bags[0], NamedSharding(eng.mesh,
                                                      P("kv", None, None)))
    ts = kv.pull_sparse(names[0], on_device, pool="sum")
    kv.wait(ts)
    assert eng._programs == programs and eng._bound == bound
    assert (_bits(kv.get_pulled(ts)) == _bits(pooled[0])).all()


def test_wrong_ranks_and_shapes_are_refused_by_name(one_shard):
    kv, eng = one_shard
    dim = 128
    for n in ("a", "b"):
        eng.register_sparse(n, 40, dim)
    flat = np.zeros((1, B), np.int32)
    bags = np.zeros((1, B, 3), np.int32)
    g = np.zeros((1, B, dim), np.float32)
    with pytest.raises(CheckError, match=r"ids must be \[W, B, h\].*rank 3"):
        kv.pull_sparse("a", flat, pool="sum")
    with pytest.raises(CheckError, match=r"ids must be \[W, B, h\].*\(1, 12\)"):
        kv.push_sparse_group(["a", "b"], [bags, flat], [g, g], pool="sum")
    with pytest.raises(CheckError, match=r"bags \[W, B, h\] go with pool"):
        kv.pull_sparse("a", bags)
    with pytest.raises(CheckError, match=r"bags \[W, B, h\] go with pool"):
        kv.pull_sparse_group(["a", "b"], [flat, bags])
    with pytest.raises(CheckError, match="unknown pool 'mean'"):
        kv.pull_sparse("a", bags, pool="mean")
    # One gradient a bag: the multiplied-out rows are refused, for the table.
    wide = np.zeros((1, B * 3, dim), np.float32)
    with pytest.raises(CheckError,
                       match=r"table 'b': gradients of shape \(1, 36, 128\), "
                             r"not \(1, 12, 128\).*one row a bag"):
        kv.push_sparse_group(["a", "b"], [bags, bags], [g, wide], HANDLE,
                             pool="sum")
    with pytest.raises(CheckError,
                       match=r"table 'a': gradients of shape \(1, 12, 64\)"):
        kv.push_sparse("a", bags, g[:, :, :64], pool="sum")
    with pytest.raises(CheckError, match="one host buffer a table"):
        kv.pull_sparse_group(["a", "b"], [bags, bags],
                             outs=[np.zeros((1, B, dim), np.float32)],
                             pool="sum")
    # What was refused bound nothing; the right call goes through, its host
    # buffers a table's pooled rows each.
    outs = [np.ones((1, B, dim), np.float32) for _ in range(2)]
    kv.wait(kv.push_sparse_group(["a", "b"], [bags, bags], [g + 1, g + 2],
                                 pool="sum"))
    kv.wait(kv.pull_sparse_group(["a", "b"], [bags, bags], outs=outs,
                                 pool="sum"))
    # Row 0 took 3 slots of each of 12 bags; a bag sums it 3 times.
    assert (outs[0] == 3 * 36 * 1.0).all() and (outs[1] == 3 * 36 * 2.0).all()


def test_the_three_counters_the_notes_and_the_spans_pool(monkeypatch):
    """A pooled op notes one ``SPARSE_POOL`` with the bags and the lookups it
    carried over all workers and tables, before its ``ENGINE_OP``; an op that
    pools nothing (no ``pool``, or bags of one id) notes none; the gauges
    ``engine.sparse.pool.ops`` / ``.bags`` / ``.lookups`` and
    ``StageClock.pooled`` over a window read them; the op's span carries
    ``pool``."""
    from pslite_tpu.telemetry.metrics import Registry

    clock = profiling.StageClock()
    monkeypatch.setattr(profiling, "_clock", clock)
    c, kv, eng = _cluster(4)
    try:
        W, dim = 4, 128
        names = _names("t")
        for n, r in zip(names, ROWS):
            eng.register_sparse(n, r, dim)
        idx, grads = _traffic(ROWS, BAGS, W, dim, seed=40)
        flat, repeated = _out(idx, grads)
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
        notes = []
        monkeypatch.setattr(eng, "_note", notes.append)
        kv.wait(kv.push_sparse_group(names, idx, grads, HANDLE, pool="sum"))
        kv.wait(kv.pull_sparse_group(names, idx, pool="sum"))
        kv.wait(kv.pull_sparse(names[3], idx[3], pool="sum"))
        kv.wait(kv.push_sparse(names[2], idx[2], grads[2], pool="sum"))
        # Not pooled: no ``pool``; bags of one id.
        kv.wait(kv.push_sparse_group(names, flat, repeated, HANDLE))
        kv.wait(kv.pull_sparse(names[1], idx[1], pool="sum"))
        pooled = [n for n in notes if n[0] == profiling.SPARSE_POOL]
        bags, lookups = W * B * len(ROWS), W * B * sum(BAGS)
        assert [n[2:] for n in pooled] == [
            (bags, lookups, -1), (bags, lookups, -1),
            (W * B, W * B * 27, -1), (W * B, W * B * 3, -1)]
        kinds = [n[0] for n in notes]
        assert kinds.count(profiling.ENGINE_OP) == 6
        for k, kind in enumerate(kinds):
            if kind == profiling.SPARSE_POOL:
                assert kinds[k + 1:k + 3] == [profiling.LAUNCH,
                                              profiling.ENGINE_OP]
        ops = [s for s in spans if s.name == profiling.OP_SPAN]
        assert [s.meta.get("pool") for s in ops] == [
            "sum", "sum", "sum", "sum", None, "sum"]
        assert ops[0].meta["handle"] == "row_adagrad"
        assert ops[0].meta["tables"] == len(ROWS)
        # The routed slots are the lookups': a shard's buckets hold 1.5 a
        # lookup of the tables that route.
        routed = [n[2] for n in notes if n[0] == profiling.SPARSE_ROUTE]
        assert routed[0] == routed[1] == sum(
            sparse._slots(W, B * h) for h in BAGS)
        for note in notes:
            clock.note(note)
        registry = Registry()
        eng.export(registry)
        gauges = registry.snapshot()["gauges"]
        assert gauges["engine.sparse.pool.ops"] == 4
        assert gauges["engine.sparse.pool.bags"] == 2 * bags + 2 * W * B
        assert gauges["engine.sparse.pool.lookups"] == (
            2 * lookups + W * B * 30)
        assert clock.pooled_totals() == (2 * bags + 2 * W * B,
                                         2 * lookups + W * B * 30, 4)
    finally:
        c.finalize()
    # Over a window, as ``grouped`` reads: whole slots of the clock.
    clock = profiling.StageClock()
    slot = 1 << profiling.StageClock.SLOT_SHIFT
    t = 100 * slot
    for k in range(6):
        end = t + k * slot + 1000
        clock.note((profiling.SPARSE_POOL, end, 106496, 876544, -1))
        clock.note((profiling.ENGINE_OP, end, 10, 20, 30))
    (bags, lookups, ops), whole, _ = clock.pooled(
        (t + slot) / 1e9, (t + 5 * slot + 10) / 1e9)
    assert (bags, lookups, ops, whole) == (4 * 106496, 4 * 876544, 4, 4)
    assert lookups / bags == pytest.approx(8.2308, abs=1e-4)
    assert clock.pooled(0.0, 1.0) == ((0, 0, 0), 0, 0.0)


def test_the_lowered_programs_carry_the_bags_scopes(one_shard):
    """``ps.sparse.pull.pool`` around the sum over a bag and
    ``ps.sparse.push.bag`` where a slot's gradient is read through its bag,
    inside the table's scope of a group program; the pooled push takes no
    ``[B * h, d]`` gradient: its gradient parameter is ``[1, B, d]``."""
    kv, eng = one_shard
    dim, h = 128, 5
    for n in ("a", "b"):
        eng.register_sparse(n, 40, dim)
    tables = [eng.table("a"), eng.table("b")]
    batches = ((B, h), B)
    f32, s32 = np.float32, np.int32
    store = jax.ShapeDtypeStruct((40, dim), f32)
    ids = [jax.ShapeDtypeStruct((1, B, h), s32),
           jax.ShapeDtypeStruct((1, B), s32)]
    g = jax.ShapeDtypeStruct((1, B, dim), f32)
    acc = jax.ShapeDtypeStruct((40,), f32)
    scalar = jax.ShapeDtypeStruct((), f32)
    pull = eng._sparse_group_program("pull", tables, batches).lower(
        store, store, *ids).as_text(debug_info=True)
    assert "ps.sparse.table.a/ps.sparse.pull.pool" in pull
    assert "ps.sparse.table.b/ps.sparse.pull.pool" not in pull
    push = eng._sparse_group_program(
        "push_row_adagrad", tables, batches).lower(
        store, store, acc, acc, *ids, g, g, scalar, scalar).as_text(
        debug_info=True)
    assert "ps.sparse.table.a/ps.sparse.combine/ps.sparse.push.bag" in push \
        or "ps.sparse.table.a/ps.sparse.push.scatter_add/ps.sparse.push.bag" \
        in push
    assert "ps.sparse.table.b/ps.sparse.combine/ps.sparse.push.bag" not in push
    assert f"tensor<1x{B * h}x{dim}xf32>" not in push.split("func.func")[1]
