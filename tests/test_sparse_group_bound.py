"""A grouped sparse op is bound once (PR 51): what no two ``pull_group`` /
``push_group`` calls of one ``(op, names, handle, batches)`` differ in is a
record (``parallel/sparse.py`` ``_Bound``, built by ``SparseEngine._bind``)
that the first call builds and the others look up, as a one-table ``push``
looks up its table's.

Held here, on the CPU, where counts are the evidence: a steady step builds
no record, constructs no ``NamedSharding``, places no input and asks none of
the per-table predicates again; bound steps leave what the same steps
through the one-table calls leave, bit for bit, whatever the inputs' kind;
everything that drops a table's record drops every group's it is a member
of; and each error of a grouped call is raised still, by the same words.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pslite_tpu.parallel import sparse  # noqa: E402
from pslite_tpu.parallel.sparse import SparseEngine  # noqa: E402
from pslite_tpu.telemetry.metrics import Registry  # noqa: E402
from pslite_tpu.utils.logging import CheckError  # noqa: E402

ROWS = 1003                 # no multiple of 4: the last shard is short
N = 48
HANDLE = "row_adagrad:0.05,1e-8"
PUSH_GAUGES = ["engine.sparse.push." + g for g in (
    "stateful", "row_kernel", "segsum_kernel", "acc_kernel", "packed")]


def _mesh(shards):
    return Mesh(np.array(jax.devices()[:shards]), ("kv",))


def _gauges(eng):
    registry = Registry()
    eng.export(registry)
    return registry.snapshot()["gauges"]


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _traffic(dims, W, seed, n=N):
    """Seeded ids and gradients a table: a row every worker asks for, a
    duplicate within a worker."""
    rng = np.random.default_rng(seed)
    idx = [rng.integers(0, ROWS, size=(W, n)).astype(np.int32) for _ in dims]
    for i in idx:
        i[:, 0] = 7
        i[:, 1] = i[:, 2]
    grads = [rng.normal(size=(W, n, d)).astype(np.float32) for d in dims]
    return idx, grads


def _on_device(eng, idx, grads):
    """The inputs as a trainer's own batch lies: what the programs take."""
    ids = NamedSharding(eng.mesh, P(eng.axis, None))
    rows = NamedSharding(eng.mesh, P(eng.axis, None, None))
    return ([jax.device_put(i, ids) for i in idx],
            [jax.device_put(g, rows) for g in grads])


def _register(eng, names, dims, seed=3):
    inits = [np.random.default_rng(seed + k).normal(
        size=(ROWS, d)).astype(np.float32) for k, d in enumerate(dims)]
    for name, d, init in zip(names, dims, inits):
        eng.register_sparse(name, ROWS, d, init=init)
    return inits


def _counted(monkeypatch, obj, name, calls):
    real = getattr(obj, name)

    def counting(*args, **kw):
        calls.append(name)
        return real(*args, **kw)

    monkeypatch.setattr(obj, name, counting)


@pytest.mark.parametrize("shards, handle", [(1, None), (1, HANDLE), (4, None)],
                         ids=["one-shard-sum", "one-shard-row_adagrad",
                              "four-shards-sum"])
def test_fifty_steps_build_two_records_and_a_steady_step_asks_nothing_again(
        shards, handle, monkeypatch):
    eng = SparseEngine(_mesh(shards))
    dims = [64, 128, 64, 64, 128, 64]
    names = [f"emb{k:02d}" for k in range(len(dims))]
    _register(eng, names, dims)
    idx, grads = _on_device(eng, *_traffic(dims, shards, seed=51))

    def step():
        pulled = eng.pull_group(names, idx)
        return pulled, eng.push_group(names, idx, grads, handle)

    step()                                   # binds: a pull's, a push's
    assert eng.group_binds == 2
    calls = []
    for name in ("_row_kernel", "_segsum_kernel", "_acc_kernel", "_platform",
                 "_route_slots", "_routed", "_bind", "_prep_ids",
                 "_prep_grads", "_sparse_group_program", "_handle_scalars"):
        _counted(monkeypatch, eng, name, calls)
    # What ``parallel/sparse.py`` constructs it imports by this name when
    # it does (jit's own shardings are made below it).
    _counted(monkeypatch, jax.sharding, "NamedSharding", calls)
    _counted(monkeypatch, sparse, "_input_shardings", calls)
    for _ in range(49):
        pulled, token = step()
    token.block_until_ready()
    assert calls == []
    assert eng.group_binds == 2 and len(eng._bound) == 2
    gauges = _gauges(eng)
    assert gauges["engine.sparse.group.binds"] == 2
    assert gauges["engine.sparse.push.stateful"] == 50 * (handle is not None)
    assert gauges["engine.sparse.push.packed"] == 50
    # The record is what the calls' counters add: plain numbers.
    push = eng._bound[("push", tuple(names), handle, (N,) * len(dims))]
    pull = eng._bound[("pull", tuple(names), None, (N,) * len(dims))]
    payload = shards * N * sum(dims) * 4
    assert push.payload == pull.payload == payload
    assert (eng.push_bytes, eng.pull_bytes) == (50 * payload, 50 * payload)
    assert push.order == pull.order == tuple(sorted(names))
    assert push.slots == pull.slots == len(dims) * sparse._slots(shards, N)
    assert len(pulled) == len(dims)


def _mixed(eng, idx, grads):
    """One group's inputs of every kind: as the program takes them, on the
    device in another dtype or layout, numpy, numpy of another dtype."""
    dev_i, dev_g = _on_device(eng, idx, grads)
    kinds_i = [dev_i[0], idx[1], idx[2].astype(np.int64),
               jnp.asarray(idx[3]), dev_i[4], idx[5].astype(np.uint16)]
    kinds_g = [dev_g[0], dev_g[1], grads[2].astype(np.float64),
               jnp.asarray(grads[3]), grads[4],
               jnp.asarray(grads[5]).astype(jnp.bfloat16)]
    return kinds_i, kinds_g


@pytest.mark.parametrize("handle", [None, HANDLE], ids=["sum", "row_adagrad"])
@pytest.mark.parametrize(
    "shards, kernels", [(1, False), (1, True), (4, False)],
    # Interpreted over four shards the kernels take three minutes a case.
    ids=["one-shard-xla", "one-shard-kernels-interpreted", "four-shards-xla"])
def test_bound_steps_are_the_one_table_calls_bit_for_bit(shards, kernels,
                                                         handle,
                                                         monkeypatch):
    """Four bound steps of a group of lane-packed (64) and unpacked (128)
    tables fed inputs of every kind, beside the same steps a table at a
    time on a twin engine: stores, accumulators, pulled rows and byte
    counters equal; a push counter rises once an op where the twin's rises
    at all (one push, whatever it groups)."""
    if kernels:
        for rule in (sparse._ROW_ADD_INTERPRET, sparse._SEGMENT_SUM_INTERPRET,
                     sparse._ACC_UPDATE_INTERPRET):
            monkeypatch.setitem(rule, "cpu", True)
    dims = [64, 128, 64, 128, 64, 128]
    names = [f"t{k}" for k in range(len(dims))]
    eng, twin = SparseEngine(_mesh(shards)), SparseEngine(_mesh(shards))
    for e in (eng, twin):
        _register(e, names, dims)
    idx, grads = _traffic(dims, shards, seed=52)
    # bf16 gradients reach both engines as the same f32 values.
    grads[5] = np.asarray(
        jnp.asarray(grads[5]).astype(jnp.bfloat16).astype(jnp.float32))
    kinds_i, kinds_g = _mixed(eng, idx, grads)
    steps = 5                                # the first binds, four bound
    for s in range(steps):
        pulled = eng.pull_group(names, kinds_i)
        token = eng.push_group(names, kinds_i, kinds_g, handle)
        solo = [twin.pull(n, i) for n, i in zip(names, idx)]
        for n, i, g in zip(names, idx, grads):
            twin.push(n, i, g, handle)
        for n, got, want in zip(names, pulled, solo):
            assert (_bits(got) == _bits(want)).all(), (s, n)
    token.block_until_ready()
    assert eng.group_binds == 2
    for n in names:
        assert (_bits(eng.store_raw(n)) == _bits(twin.store_raw(n))).all(), n
        if handle is not None:
            assert (_bits(eng._acc[n]) == _bits(twin._acc[n])).all(), n
    assert (eng.push_bytes, eng.pull_bytes) == (twin.push_bytes,
                                                twin.pull_bytes)
    assert eng.push_bytes == steps * shards * N * sum(dims) * 4
    ours, theirs = _gauges(eng), _gauges(twin)
    for g in PUSH_GAUGES:
        assert ours[g] == steps * (theirs[g] > 0), g
    if kernels:
        assert ours["engine.sparse.push.row_kernel"] == steps
    assert ours["engine.sparse.push.packed"] == steps


@pytest.mark.parametrize("cause", ["register", "reshard", "pack"])
def test_what_drops_a_tables_record_drops_every_group_of_it(cause):
    """Three groups, ``[a, b]``, ``[b, c]`` and ``[c, d]``, each bound for
    its pull and its push.  A new registration of ``b``, or its packing
    changed, drops the two groups ``b`` is a member of and leaves the third;
    a reshard drops all.  The next ops rebuild what was dropped, no more,
    and read and write the tables as they are then (float64 beside them)."""
    W = 4
    eng = SparseEngine(_mesh(W))
    names, dims = ["a", "b", "c", "d"], [64, 64, 128, 64]
    ref = {n: init.astype(np.float64)
           for n, init in zip(names, _register(eng, names, dims))}
    groups = [["a", "b"], ["b", "c"], ["c", "d"]]
    width = dict(zip(names, dims))

    def run(W):
        for k, group in enumerate(groups):
            idx, grads = _traffic([width[n] for n in group], W, seed=53 + k)
            dev_i, dev_g = _on_device(eng, idx, grads)
            eng.push_group(group, dev_i, dev_g)
            for n, i, g in zip(group, idx, grads):
                np.add.at(ref[n], i.reshape(-1), g.reshape(-1, width[n]))
            for n, i, rows in zip(group, idx, eng.pull_group(group, dev_i)):
                assert np.allclose(np.asarray(rows), ref[n][i],
                                   atol=1e-4), (cause, group, n)

    run(W)
    assert eng.group_binds == 6 and len(eng._bound) == 6
    if cause == "register":
        ref["b"] = _register(eng, ["b"], [64], seed=9)[0].astype(np.float64)
        dropped = 4
    elif cause == "pack":
        assert eng.table("b").pack == 2
        with eng._table_mu["b"]:
            eng._ensure_unpacked("b")
        assert eng.table("b").pack == 1
        dropped = 4
    else:
        W = 2
        eng.reshard(_mesh(W))
        dropped = 6
    assert len(eng._bound) == 6 - dropped
    assert all("b" not in key[1] for key in eng._bound)
    run(W)
    assert eng.group_binds == 6 + dropped and len(eng._bound) == 6
    run(W)                                   # bound again: nothing is built
    assert eng.group_binds == 6 + dropped


@pytest.mark.parametrize("error", ["length", "twice", "worker-dim-device",
                                   "worker-dim-host", "handle"])
def test_a_bound_key_fails_as_an_unbound_one_by_the_same_words(error):
    W = 2
    eng = SparseEngine(_mesh(W))
    names, dims = ["users", "items", "ads"], [64, 128, 64]
    _register(eng, names, dims)
    idx, grads = _traffic(dims, W, seed=54)
    dev_i, dev_g = _on_device(eng, idx, grads)
    for _ in range(2):                       # bound, and launched bound
        eng.pull_group(names, dev_i)
        eng.push_group(names, dev_i, dev_g)
        eng.push_group(names, dev_i, dev_g, HANDLE)
    assert eng.group_binds == 3
    wide = np.concatenate([idx[1], idx[1]])                  # [2W, n]
    if error == "length":
        with pytest.raises(CheckError, match="group length mismatch"):
            eng.push_group(names, dev_i, dev_g[:2])
        with pytest.raises(CheckError, match="group length mismatch"):
            eng.pull_group(names, dev_i[:2])
    elif error == "twice":
        twice = ["items", "users", "items"]
        with pytest.raises(CheckError, match=r"\['items'\] appear twice in "
                           r"one grouped push: a table's store is donated"):
            eng.push_group(twice, [dev_i[1], dev_i[0], dev_i[1]],
                           [dev_g[1], dev_g[0], dev_g[1]])
        # A pull donates nothing: the same table twice reads it twice.
        assert len(eng.pull_group(twice, [dev_i[1], dev_i[0], dev_i[1]])) == 3
    elif error == "worker-dim-device":
        lying = jax.device_put(wide, NamedSharding(eng.mesh, P("kv", None)))
        with pytest.raises(CheckError, match="bad worker dim"):
            eng.pull_group(names, [dev_i[0], lying, dev_i[2]])
        with pytest.raises(CheckError, match="bad worker dim"):
            eng.push_group(names, [dev_i[0], lying, dev_i[2]], dev_g)
    elif error == "worker-dim-host":
        with pytest.raises(CheckError, match="bad worker dim"):
            eng.pull_group(names, [dev_i[0], wide, dev_i[2]])
    else:
        with pytest.raises(CheckError,
                           match="unknown sparse handle 'adam:0.1'"):
            eng.push_group(names, dev_i, dev_g, "adam:0.1")
    # Nothing was bound by a call that failed, no lock is left taken, and
    # the bound keys launch as before.
    assert eng.group_binds == 3 + (error == "twice")
    for name in names:
        assert eng._table_mu[name].acquire(blocking=False)
        eng._table_mu[name].release()
    before = eng.push_bytes
    eng.push_group(names, dev_i, dev_g).block_until_ready()
    assert eng.push_bytes > before and eng.group_binds == 3 + (error == "twice")
