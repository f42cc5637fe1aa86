"""A sparse pull is one program: ``SparseEngine.pull`` and ``pull_group``
return the very arrays their program returned, ``[W, n, d]`` sharded a
worker's batch to its device (``P(axis, None, None)``), and no reshape after
it launches a second program and copies the batch once more (PR 49; since
PR 53 ``pull_group``'s are one array a width, held by a ``PulledGroup``).

What the caller sees is what it saw before: every pulled row is the stored
row bit for bit, each worker's batch on its own device.  Held here on every
shape of the path: one shard, four shards routed by owner, a batch that
overflows into the gathered body, a lane-packed table, ``pull_group`` over two
tables of different width, and ``kv.pull_sparse(..., out=host_buffer)``.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pslite_tpu import KVWorker  # noqa: E402
from pslite_tpu.parallel.sparse import PulledGroup, SparseEngine  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

ROWS, N = 1003, 128         # 1003: no multiple of 4, the last shard short


def _mesh(shards):
    return Mesh(np.array(jax.devices()[:shards]), ("kv",))


def _table(dim, seed=49):
    return np.random.default_rng(seed).normal(
        size=(ROWS, dim)).astype(np.float32)


def _batch(shards, seed=49):
    """Spread over the owners (every op routed where the mesh routes), with
    a row every worker asks for and a duplicate within a worker."""
    idx = np.random.default_rng(seed).integers(
        0, ROWS, size=(shards, N)).astype(np.int32)
    idx[:, 0] = 7
    idx[:, 1] = idx[:, 2]
    return idx


def _one_owner(shards, seed=50):
    """Every id of every worker on shard 1: no bucket holds them, the op
    takes the gathered body of the same program."""
    rng = np.random.default_rng(seed)
    idx = (rng.integers(0, ROWS // shards, size=(shards, N)) * shards
           + 1).astype(np.int32)
    assert idx.max() < ROWS
    return idx


def _spy(eng, op="pull"):
    """Wrap the programs the engine keeps for ``op``: what each call of one
    returned, in order."""
    returned = []

    def wrap(prog):
        def spied(*args):
            out = prog(*args)
            returned.append(out)
            return out
        return spied

    keys = [k for k in eng._programs if k[0] == op]
    assert keys
    for k in keys:
        prog = eng._programs[k]
        eng._programs[k] = wrap(prog)
        # A grouped op's record holds its program (``SparseEngine._bind``).
        for b in eng._bound.values():
            if b.prog is prog:
                b.prog = eng._programs[k]
    return returned


def _is_a_workers_batch_a_device(arr, eng, n, dim):
    S = eng.num_shards
    assert arr.shape == (S, n, dim) and arr.dtype == np.float32
    want = NamedSharding(eng.mesh, P(eng.axis, None, None))
    assert arr.sharding.is_equivalent_to(want, 3), arr.sharding
    devices = list(eng.mesh.devices.flat)
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start)
    assert [s.device for s in shards] == devices
    assert all(s.data.shape == (1, n, dim) for s in shards)


CASES = {
    # shards, dim, batch, routed, overflows
    "one-shard": (1, 128, _batch, False, 0),
    "four-shards-routed": (4, 128, _batch, True, 0),
    "four-shards-overflow-to-gathered": (4, 128, _one_owner, True, 1),
    "one-shard-lane-packed-64": (1, 64, _batch, False, 0),
    "four-shards-lane-packed-64": (4, 64, _batch, True, 0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_pull_returns_its_programs_own_array(case):
    shards, dim, batch, routed, overflows = CASES[case]
    eng = SparseEngine(_mesh(shards))
    init = _table(dim)
    table = eng.register_sparse("emb", ROWS, dim, init=init)
    assert table.pack == 128 // dim
    idx = batch(shards)
    first = eng.pull("emb", idx)            # builds and keeps the program
    assert eng._routed(N) == routed and eng.route_overflows() == overflows
    returned = _spy(eng)
    pulled = eng.pull("emb", idx)
    assert len(returned) == 1               # one program, called once
    rows = returned[0][0] if routed else returned[0]
    assert pulled is rows                   # nothing launched after it
    _is_a_workers_batch_a_device(pulled, eng, N, dim)
    # A pulled row is the stored row, bit for bit: what the reshape handed
    # over before.
    got = np.asarray(pulled)
    assert (got.view(np.uint32) == init[idx].view(np.uint32)).all()
    assert (np.asarray(first) == got).all()


@pytest.mark.parametrize("shards", [1, 4], ids=["one-shard", "four-shards"])
def test_pull_group_returns_its_programs_own_arrays(shards):
    """Two tables of different width in one program: 128 lanes and a
    lane-packed 64."""
    eng = SparseEngine(_mesh(shards))
    names, dims = ["wide", "narrow"], [128, 64]
    inits = [_table(d, seed=60 + d) for d in dims]
    for n, d, init in zip(names, dims, inits):
        eng.register_sparse(n, ROWS, d, init=init)
    asked = [_batch(shards, seed=61), _batch(shards, seed=62)[:, :48]]
    eng.pull_group(names, asked)
    returned = _spy(eng)
    pulled = eng.pull_group(names, asked)
    assert len(returned) == 1 and type(pulled) is PulledGroup
    outs = returned[0]
    # Two widths, two classes: a result each, and the count where routed.
    assert len(outs) == 2 + eng._group_routed((N, 48))
    assert pulled.entries == ((0, 0, N), (1, 0, 48)) and len(pulled) == 2
    for c, (out, d, init, idx) in enumerate(zip(outs, dims, inits, asked)):
        assert pulled.arrays[c] is out      # nothing launched after it
        _is_a_workers_batch_a_device(out, eng, idx.shape[1], d)
        assert (np.asarray(pulled[c]).view(np.uint32)
                == init[idx].view(np.uint32)).all()


@pytest.fixture()
def cluster():
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.workers[0].van.set_mesh(_mesh(4))
    c.start()
    kv = KVWorker(0, 0, postoffice=c.workers[0])
    yield kv, kv.po.van.sparse_engine
    c.finalize()


@pytest.mark.parametrize("dim", [128, 64], ids=["unpacked", "lane-packed"])
@pytest.mark.parametrize("batch", [_batch, _one_owner],
                         ids=["routed", "overflow-to-gathered"])
def test_pull_sparse_copies_the_programs_result_out(cluster, dim, batch):
    """``out=`` goes through ``_engine_complete``, which flattens what it is
    given: the host buffer holds each worker's rows in the batch's order,
    and ``get_pulled`` hands over the program's own array."""
    kv, eng = cluster
    init = _table(dim)
    eng.register_sparse("emb", ROWS, dim, init=init)
    idx = batch(4)
    kv.wait(kv.pull_sparse("emb", idx, out=np.zeros((4, N, dim), np.float32)))
    returned = _spy(eng)
    out = np.full((4, N, dim), np.nan, np.float32)
    kv.wait(kv.pull_sparse("emb", idx, out=out))
    assert (out.view(np.uint32) == init[idx].view(np.uint32)).all()
    # A flat buffer of the same size is filled the same way.
    flat = np.zeros(4 * N * dim, np.float32)
    kv.wait(kv.pull_sparse("emb", idx, out=flat))
    assert (flat.reshape(4, N, dim) == out).all()
    ts = kv.pull_sparse("emb", idx, out=None)
    kv.wait(ts)
    assert len(returned) == 3
    assert kv.get_pulled(ts) is returned[-1][0]
    _is_a_workers_batch_a_device(kv.get_pulled(ts), eng, N, dim)
