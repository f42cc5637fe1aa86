"""A table sharded over four servers with four workers, through ``KVWorker``
on four virtual CPU devices: what the cell ``dlrm-criteo-emb.zipf.4chip``
runs at a chip-filling size, held here at a tiny one.

Four workers push seeded Zipf batches (duplicates within and across workers,
the hottest row from every worker) for three steps.  The four shards' stores
add up to one uncut float64 table, every row ``r`` on shard ``r % 4`` at local
row ``r // 4`` and nowhere else (``model-configs`` section 4's tie of a share
to the whole); each worker's pull returns its own batch's rows; the lowered
programs carry the exchange's scopes; the counter ``engine.sparse.route.slots``
says how many slots a shard works on.
"""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh  # noqa: E402

from pslite_tpu import KVWorker  # noqa: E402
from pslite_tpu.utils import profiling  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

W = 4                       # servers = workers = shards
ROWS, N, STEPS = 203, 48, 3    # 203: no multiple of 4, the last shard short
HOT = 7                     # the hottest row: every worker, several copies
ROUTE = "ps.sparse.route"
SCOPES = {"push": (".ids", ".grads"), "pull": (".ids", ".rows")}


@pytest.fixture()
def cluster():
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.workers[0].van.set_mesh(Mesh(np.array(jax.devices()[:W]), ("kv",)))
    c.start()
    kv = KVWorker(0, 0, postoffice=c.workers[0])
    yield kv, kv.po.van.sparse_engine
    c.finalize()


def _zipf(rng, shape):
    """Bounded Zipf(0.99) ranks scrambled over the rows, rank 0 at HOT."""
    p = np.arange(1, ROWS + 1, dtype=np.float64) ** -0.99
    ranks = rng.choice(ROWS, size=shape, p=p / p.sum())
    return ((ranks * 37 + HOT) % ROWS).astype(np.int32)   # gcd(37, 203) = 1


def _traffic(dim, seed=47):
    rng = np.random.default_rng(seed)
    idx = [_zipf(rng, (W, N)) for _ in range(STEPS)]
    for batch in idx:
        batch[:, 0] = HOT               # every worker sends the hottest row
        batch[:, 1] = batch[:, 2]       # a duplicate within a worker
    init = rng.normal(size=(ROWS, dim)).astype(np.float32)
    grads = [rng.normal(size=(W, N, dim)).astype(np.float32)
             for _ in range(STEPS)]
    return idx, init, grads


def _reference(init, idx, grads):
    """One uncut table in float64: every slot of every worker added once."""
    table = np.asarray(init, np.float64).copy()
    for i, g in zip(idx, grads):
        np.add.at(table, i.reshape(-1),
                  np.asarray(g, np.float64).reshape(-1, table.shape[1]))
    return table


def _row_error(got, want):
    diff = np.abs(np.asarray(got, np.float64) - want).max(axis=-1)
    return float((diff / np.maximum(np.abs(want).max(axis=-1), 1.0)).max())


# f32 sums of at most a few dozen gradients a row against float64.
TOL = 2e-5


def _push_all(kv, eng, dim, group):
    idx, init, grads = _traffic(dim)
    eng.register_sparse("emb", ROWS, dim, init=init)
    if group:
        eng.register_sparse("twin", ROWS, dim, init=init)
    for i, g in zip(idx, grads):
        if group:
            token = eng.push_group(["emb", "twin"], [i, i], [g, g])
        else:
            ts = kv.push_sparse("emb", i, g)
    if group:
        jax.block_until_ready(token)
    else:
        kv.wait(ts)
    return idx, init, grads


@pytest.mark.parametrize("group", [False, True], ids=["single", "group"])
@pytest.mark.parametrize("dim", [128, 8], ids=["unpacked", "lane-packed"])
def test_the_four_shards_add_up_to_the_uncut_table(cluster, dim, group):
    kv, eng = cluster
    idx, init, grads = _push_all(kv, eng, dim, group)
    want = _reference(init, idx, grads)
    touched = np.unique(np.concatenate([i.reshape(-1) for i in idx]))
    quiet = np.setdiff1d(np.arange(ROWS), touched)
    assert len(quiet) >= 10 and HOT in touched
    for name in ("emb", "twin") if group else ("emb",):
        table = eng.table(name)
        rps = table.rows_per_shard
        assert rps == -(-(-(-ROWS // W)) // table.pack) * table.pack
        # store_array: the logical shard-interleaved layout, shard s's rows
        # first to last; taken apart here by hand, not by the engine's own
        # de-interleave.
        shards = np.asarray(eng.store_array(name)).reshape(W, rps, dim)
        seen = np.zeros(ROWS, bool)
        for s in range(W):
            for local in range(rps):
                r = local * W + s
                if r >= ROWS:           # the table's ragged end: padding
                    assert not shards[s, local].any()
                    continue
                seen[r] = True
                assert _row_error(shards[s, local], want[r]) < TOL, (r, s)
        assert seen.all()
        # A row no push touched is its initial value, bit for bit: nothing
        # of another row's gradient reached it on any shard.
        got = shards.transpose(1, 0, 2).reshape(rps * W, dim)[:ROWS]
        assert (got[quiet] == init[quiet]).all()


@pytest.mark.parametrize("group", [False, True], ids=["single", "group"])
def test_each_worker_pulls_its_own_batch_and_the_hot_row_has_one_owner(
        cluster, group):
    kv, eng = cluster
    dim = 128
    idx, init, grads = _push_all(kv, eng, dim, group)
    want = _reference(init, idx, grads)
    ask = idx[-1]                       # [W, N]: another batch a worker
    assert len({tuple(row) for row in ask}) == W
    if group:
        pulled = [np.asarray(p) for p in eng.pull_group(["emb", "twin"],
                                                        [ask, ask])]
    else:
        out = np.zeros((W, N, dim), np.float32)
        kv.wait(kv.pull_sparse("emb", ask, out=out))
        pulled = [out]
    for got in pulled:
        assert got.shape == (W, N, dim)
        for w in range(W):
            assert _row_error(got[w], want[ask[w]]) < TOL, w
        # Every copy of the hottest row, over all four workers' rows, is
        # the one aggregated row of its one owner.
        hot = got[ask == HOT]
        assert len(hot) >= W and (hot == hot[0]).all()
        assert HOT % W == 3             # it lies on the last shard alone


def _lowered(eng, op, group):
    import jax.numpy as jnp

    idx = jnp.zeros((W, N), jnp.int32)
    g = jnp.zeros((W, N, 128), jnp.float32)
    names = ["emb", "twin"] if group else ["emb"]
    tables = [eng.register_sparse(n, ROWS, 128) for n in names]
    stores = [eng._stores[n] for n in names]
    k = len(names)
    prog = (eng._sparse_group_program(op, tables, (N,) * k) if group
            else eng._sparse_program(op, tables[0], N))
    args = stores + [idx] * k + ([g] * k if op == "push" else [])
    return prog.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("group", [False, True], ids=["single", "group"])
@pytest.mark.parametrize("op", ["push", "pull"])
def test_the_lowered_programs_carry_the_exchanges_scopes(cluster, op, group):
    _, eng = cluster
    text = _lowered(eng, op, group)
    for leaf in SCOPES[op]:
        lines = [l for l in text.splitlines() if ROUTE + leaf in l]
        assert lines, leaf
        # Nested: the outer scope stays, the leaf lies inside it.
        assert all(f"{ROUTE}/{ROUTE}{leaf}" in l for l in lines), leaf
    other = {".grads": "pull", ".rows": "push"}
    for leaf, not_in in other.items():
        assert (ROUTE + leaf in text) == (op != not_in)
    # Each collective is in the scope that names it: the text names an
    # operation's place ``<scopes>/<primitive>``.
    places = set(re.findall(r'loc\("(?:[^"]*?/)??(ps\.sparse\.[^"]*)"', text))
    gathers = {p for p in places if p.endswith("/all_gather")}
    want = {f"{ROUTE}/{ROUTE}.ids/all_gather"}
    if op == "push":
        want.add(f"{ROUTE}/{ROUTE}.grads/all_gather")
    assert gathers == want
    scatters = {p for p in places if p.endswith("scatter")}
    assert scatters == ({f"{ROUTE}/{ROUTE}.rows/reduce_scatter"}
                        if op == "pull" else set())


@pytest.mark.parametrize("group", [False, True], ids=["single", "group"])
def test_the_slots_counter_reads_w_times_n_an_op_and_is_exported(cluster,
                                                                 group):
    kv, eng = cluster
    clock = profiling.stage_clock()
    gauge = lambda: kv.po.metrics.snapshot()["gauges"][
        "engine.sparse.route.slots"]
    slots0, ops0 = clock.routed_totals()
    assert gauge() == slots0
    idx, _, grads = _traffic(128)
    eng.register_sparse("emb", ROWS, 128)
    k = 1
    if group:
        k = 2
        eng.register_sparse("twin", ROWS, 128)
        jax.block_until_ready(
            eng.push_group(["emb", "twin"], [idx[0]] * 2, [grads[0]] * 2))
    else:
        kv.wait(kv.push_sparse("emb", idx[0], grads[0]))
        # The record the push was bound to holds it, from shapes alone.
        assert eng._bound[("emb", None, N)].slots == W * N
    # One op, one note: W x n slots a table, whatever share a shard owns.
    assert clock.routed_totals() == (slots0 + k * W * N, ops0 + 1)
    assert gauge() == slots0 + k * W * N
    if group:
        eng.pull_group(["emb", "twin"], [idx[1]] * 2)
    else:
        kv.wait(kv.pull_sparse("emb", idx[1],
                               out=np.zeros((W, N, 128), np.float32)))
    assert clock.routed_totals() == (slots0 + 2 * k * W * N, ops0 + 2)
    # A smaller batch is another op's count, not the bound one's.
    kv.wait(kv.push_sparse("emb", idx[2][:, :16], grads[2][:, :16]))
    assert clock.routed_totals() == (slots0 + 2 * k * W * N + W * 16,
                                     ops0 + 3)
    assert gauge() == clock.routed_totals()[0]
