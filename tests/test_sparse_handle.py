"""A stateful server handle on sparse rows, reached through
``KVWorker.push_sparse(name, indices, grads, handle)`` -> ``_engine_op`` ->
``SparseEngine.push``: row-wise Adagrad against a plain float64 reference,
the plain sum left as it was, the record a ``(table, handle, batch)`` is
bound to, and the counters and the span that say a push ran under a handle.
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
    # The accumulator is sharded like the table: 1/W of it a device.
    shards = eng._acc["emb"].addressable_shards
    rps = eng.table("emb").rows_per_shard
    assert len(shards) == W and {s.data.shape for s in shards} == {(rps,)}
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


@pytest.mark.parametrize("cluster", [1, 4], indirect=True)
def test_the_row_kernel_in_the_push_is_bit_equal_to_xlas_scatter(
        cluster, monkeypatch):
    """On the CPU a stateful push writes the table with XLA's scatter; the
    test names the CPU among the kernel's platforms (interpreted) and the
    same three pushes give the same bits, rows and accumulator.  The
    counter follows the program: whole 128-lane f32 rows under a handle."""
    from pslite_tpu.ops import row_add as row_add_module
    from pslite_tpu.parallel import sparse

    kv, eng = cluster
    W = eng.num_shards
    idx, init, grads = _traffic(W, 128)
    twin = SparseEngine(eng.mesh, eng.axis)
    twin.register_sparse("emb", ROWS, 128, init=init)
    for g in grads:
        twin.push("emb", idx, g, HANDLE)
    assert (twin.stateful_pushes, twin.row_kernel_pushes) == (3, 0)

    traced = []
    real = row_add_module.row_add
    monkeypatch.setattr(
        row_add_module, "row_add",
        lambda store, *a, **kw: traced.append(store.shape) or real(
            store, *a, **kw))
    monkeypatch.setitem(sparse._ROW_ADD_INTERPRET, "cpu", True)
    eng.register_sparse("emb", ROWS, 128, init=init)
    eng.register_sparse("packed", ROWS, 8, init=init[:, :8])
    # A row wider than one tile keeps the scatter (Mosaic refuses the
    # kernel's one-row slice of it: ops/row_add.py).
    wide = eng.register_sparse("wide", ROWS, 256)
    assert eng._row_kernel(eng.table("emb")) and not eng._row_kernel(wide)
    assert not eng._row_kernel(eng.table("packed"))
    for g in grads:
        ts = kv.push_sparse("emb", idx, g, HANDLE)
    kv.wait(ts)
    rps = eng.table("emb").rows_per_shard
    assert traced and set(traced) == {(rps, 128)}       # in the program
    assert (eng.store_array("emb") == twin.store_array("emb")).all()
    assert (np.asarray(eng._acc["emb"]) == np.asarray(twin._acc["emb"])).all()
    assert _gauges(kv)["engine.sparse.push.row_kernel"] == 3
    # Not for a lane-packed table, nor for a push with no handle.
    kv.wait(kv.push_sparse("packed", idx, grads[0][..., :8], HANDLE))
    kv.wait(kv.push_sparse("emb", idx, grads[0]))
    assert set(traced) == {(rps, 128)}
    after = _gauges(kv)
    assert after["engine.sparse.push.stateful"] == 4
    assert after["engine.sparse.push.row_kernel"] == 3


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
    rps = eng.table("emb").rows_per_shard
    assert after["engine.sparse.acc.bytes"] == 4 * rps * W
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
    text = prog.lower(
        eng._stores["emb"], eng._acc["emb"], jnp.zeros((W, 4), jnp.int32),
        jnp.zeros((W, 4, 8), jnp.float32), jnp.float32(LR), jnp.float32(EPS)
    ).as_text(debug_info=True)
    for scope in ("ps.sparse.route", "ps.sparse.combine", "ps.update",
                  "ps.sparse.push.scatter_add"):
        assert scope in text, scope
    # The sort and the segment sum are the combine's, not the update's.
    sort_lines = [l for l in text.splitlines() if "sort" in l
                  and "ps." in l]
    assert sort_lines and all("ps.sparse.combine" in l for l in sort_lines)
