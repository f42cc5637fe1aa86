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

The exchange is routed by owner (``parallel/sparse.py`` ``_exchange``): a
shard is sent ``S * C`` slots in buckets of ``C = 1.5 x n / S``.  Held here:
the routed program's store, accumulator and pulled rows are the gathered
body's bit for bit; a batch that does not fit its buckets falls back in the
same program, stays exact and is counted by ``engine.sparse.route.overflow``;
one shard lowers no collective; two shards route as four do.
"""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh  # noqa: E402

from pslite_tpu import KVWorker  # noqa: E402
from pslite_tpu.parallel import sparse  # noqa: E402
from pslite_tpu.parallel.sparse import SparseEngine  # noqa: E402
from pslite_tpu.utils import profiling  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

W = 4                       # servers = workers = shards
ROWS, N, STEPS = 1003, 128, 3   # 1003: no multiple of 4, the last shard short
HOT = 7                     # the hottest row: every worker, several copies
C = 48                      # a (worker, owner) bucket: 1.5 x N / W slots
ROUTE = "ps.sparse.route"
SCOPES = {"push": (".ids", ".grads"), "pull": (".ids", ".rows")}
ROUTED, GATHERED = "cond/branch_1_fun/", "cond/branch_0_fun/"
LR, EPS = 0.05, 1e-6


@pytest.fixture()
def cluster():
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.workers[0].van.set_mesh(Mesh(np.array(jax.devices()[:W]), ("kv",)))
    c.start()
    kv = KVWorker(0, 0, postoffice=c.workers[0])
    yield kv, kv.po.van.sparse_engine
    c.finalize()


def _zipf(rng, shape):
    """Bounded Zipf(0.8) ranks scrambled over the rows, rank 0 at HOT: the
    hottest row ~7% of the lookups and ~58% of a step's slots distinct rows,
    about the cell's own shares (5.3%, 47%).  At 0.99 a table this small
    has a hottest row of a seventh of all lookups, and at 48 lookups a
    worker the counts' own scatter fills a bucket: with these the fullest
    (worker, owner) bucket of the seeded batches holds 42 of ``C`` = 48."""
    p = np.arange(1, ROWS + 1, dtype=np.float64) ** -0.8
    ranks = rng.choice(ROWS, size=shape, p=p / p.sum())
    return ((ranks * 37 + HOT) % ROWS).astype(np.int32)   # gcd(37, 1003) = 1


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
    args = (stores + [idx] * k + ([g] * k if op == "push" else [])
            + [eng._overflow_count("emb")])
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
    # operation's place ``<scopes>/<primitive>``, and a branch of the
    # program's one conditional ahead of the scopes.
    places = set(re.findall(r'loc\("([^"]*ps\.sparse\.[^"]*)"', text))
    if group:
        # A group program's bodies lie a table each inside
        # ``ps.sparse.group/ps.sparse.table.<name>`` (PR 50); within it a
        # body is the one-table program's.
        table = re.compile(r"^ps\.sparse\.group/ps\.sparse\.table\."
                           r"(emb|twin)/")
        # (a function the body calls names its places from its own start)
        assert {m.group(1) for m in map(table.match, places) if m} == {
            "emb", "twin"}
        # (and a pull's rows are put side by side in the group's own scope,
        # one result a class: ``PulledGroup``)
        whole = {"ps.sparse.group/concatenate",
                 "ps.sparse.group/broadcast_in_dim"} if op == "pull" else set()
        assert whole <= places
        places -= whole
        assert all(table.match(p) for p in places
                   if not p.startswith("cond/")), places
        places = {table.sub("", p) for p in places}
    routed = {p[len(ROUTED):] for p in places if p.startswith(ROUTED)}
    gathered = {p[len(GATHERED):] for p in places if p.startswith(GATHERED)}
    last = ".grads" if op == "push" else ".rows"
    # The body the program is bound to: ids out and gradient rows out, or
    # ids out and rows back, each one all_to_all; no all-gather, and in the
    # pull no reduction of rows at all.  The routed body's collectives are
    # named after their HLO opcodes (``sparse._by_opcode``), as a device
    # trace names the ones the compiler makes.
    assert {p for p in routed if p.endswith("/all-to-all")} == {
        f"{ROUTE}/{ROUTE}.ids/all-to-all", f"{ROUTE}/{ROUTE}{last}/all-to-all"}
    assert not {p for p in places if p.endswith(("/all_to_all", "/pmax"))}
    assert not {p for p in routed
                if p.endswith(("all_gather", "reduce_scatter", "psum"))}
    # The bucketing and its verdict are ahead of the conditional, in the
    # scope of the ids they sort: every shard reaches the same verdict.
    for prim in ("sort", "all-reduce"):
        assert f"{ROUTE}/{ROUTE}.ids/{prim}" in places, prim
    # The body of the batch that does not fit: the gathered form whole.
    want = {f"{ROUTE}/{ROUTE}.ids/all_gather"}
    if op == "push":
        want.add(f"{ROUTE}/{ROUTE}.grads/all_gather")
    assert {p for p in gathered if p.endswith("/all_gather")} == want
    assert {p for p in gathered if p.endswith("scatter")} == (
        {f"{ROUTE}/{ROUTE}.rows/reduce_scatter"} if op == "pull" else set())
    assert not {p for p in gathered if p.endswith("/all-to-all")}


@pytest.mark.parametrize("group", [False, True], ids=["single", "group"])
def test_the_slots_counter_reads_s_times_c_an_op_and_is_exported(cluster,
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
        assert eng._bound[("emb", None, N)].slots == W * C
        assert eng._bound[("emb", None, N)].routed
    # One op, one note: S x C slots a table, four buckets of 1.5 x n / S
    # (W x n in the gathered form), whatever the batch's own counts are.
    assert W * C == 192 < W * N
    assert clock.routed_totals() == (slots0 + k * W * C, ops0 + 1)
    assert gauge() == slots0 + k * W * C
    if group:
        eng.pull_group(["emb", "twin"], [idx[1]] * 2)
    else:
        kv.wait(kv.pull_sparse("emb", idx[1],
                               out=np.zeros((W, N, 128), np.float32)))
    assert clock.routed_totals() == (slots0 + 2 * k * W * C, ops0 + 2)
    # A smaller batch is another op's count, not the bound one's: 16
    # lookups a worker go in buckets of 6.
    kv.wait(kv.push_sparse("emb", idx[2][:, :16], grads[2][:, :16]))
    assert clock.routed_totals() == (slots0 + 2 * k * W * C + W * 6,
                                     ops0 + 3)
    assert gauge() == clock.routed_totals()[0]
    # A batch of one lookup has nothing to shrink (a bucket is a slot at
    # the least) and keeps the gathered form's count and program.
    kv.wait(kv.push_sparse("emb", idx[2][:, :1], grads[2][:, :1]))
    assert not eng._bound[("emb", None, 1)].routed
    assert clock.routed_totals()[0] == slots0 + 2 * k * W * C + W * 6 + W



# -- routed by owner against the gathered body --------------------------------

HANDLES = {"sum": None, "row_adagrad": f"row_adagrad:{LR},{EPS}"}


def _mesh(shards):
    return Mesh(np.array(jax.devices()[:shards]), ("kv",))


def _run(eng, dim, handle, group, idx, init, grads):
    """Three pushes and a pull through ``eng``: every table's store, its
    accumulator under a handle, and the rows each worker pulled."""
    names = ["emb", "twin"] if group else ["emb"]
    for n in names:
        eng.register_sparse(n, ROWS, dim, init=init)
    for i, g in zip(idx, grads):
        if group:
            eng.push_group(names, [i] * 2, [g] * 2, handle=handle)
        else:
            eng.push("emb", i, g, handle)
    pulled = (eng.pull_group(names, [idx[-1]] * 2) if group
              else [eng.pull("emb", idx[-1])])
    return ([np.asarray(eng.store_array(n)) for n in names],
            [np.asarray(eng.acc_array(n)) for n in names] if handle else [],
            [np.asarray(p) for p in pulled])


def _gathered_engine(monkeypatch, mesh):
    """An engine whose programs hold the gathered body alone: the rule that
    binds the routed one says no while it traces."""
    monkeypatch.setattr(sparse, "_routes", lambda S, n: False)
    return SparseEngine(mesh)


# XLA's scatters every shape; the kernels a TPU's lowering takes, named for
# the CPU, on the single 128-lane table they serve and under the handle,
# whose push runs all three (the sum's runs two of them the same way).
CASES = [(h, dim, group, False) for h in HANDLES for dim in (128, 8)
         for group in (False, True)] + [("row_adagrad", 128, False, True)]


@pytest.mark.parametrize(
    "handle, dim, group, kernels", CASES,
    ids=["-".join((h, "unpacked" if dim == 128 else "lane-packed",
                   "group" if group else "single",
                   "kernels" if kernels else "xla"))
         for h, dim, group, kernels in CASES])
def test_the_routed_program_is_the_gathered_one_bit_for_bit(
        monkeypatch, handle, dim, group, kernels):
    """Buckets keep the batch's order and arrive worker-major, so the
    owner's stable sort meets its slots in the gathered body's order: every
    f32 sum is added in the same order.  With the kernels a TPU's lowering
    takes named for the CPU (interpreted; the segment sum in blocks of 16
    sorted slots, so that a row's slots span blocks) as without."""
    if kernels:
        from pslite_tpu.ops import segment_sum as segment_sum_module

        monkeypatch.setattr(segment_sum_module, "_BLOCK", 16)
        for table in (sparse._ROW_ADD_INTERPRET,
                      sparse._SEGMENT_SUM_INTERPRET,
                      sparse._ACC_UPDATE_INTERPRET):
            monkeypatch.setitem(table, "cpu", True)
    traffic = _traffic(dim)
    if kernels:             # interpreted kernels are slow: one step of three
        traffic = tuple(part[:1] if isinstance(part, list) else part
                        for part in traffic)
    routed = SparseEngine(_mesh(W))
    got = _run(routed, dim, HANDLES[handle], group, *traffic)
    assert routed.route_overflows() == 0
    assert routed._route_slots(N) == W * C
    with monkeypatch.context() as m:
        gathered = _gathered_engine(m, _mesh(W))
        want = _run(gathered, dim, HANDLES[handle], group, *traffic)
        assert gathered._route_slots(N) == W * N and not gathered._overflow
    for part_got, part_want in zip(got, want):
        assert len(part_got) == len(part_want)
        for a, b in zip(part_got, part_want):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert (a.view(np.uint32) == b.view(np.uint32)).all()
    # A pulled row is the stored row, bit for bit: no reduction touched it.
    idx = traffic[0]
    store = got[0][0].reshape(W, -1, dim)
    pulled = got[2][0]
    assert (pulled == store[idx[-1] % W, idx[-1] // W]).all()


def _gauges(kv):
    return kv.po.metrics.snapshot()["gauges"]


@pytest.mark.parametrize("handle", list(HANDLES))
def test_a_batch_of_one_owner_falls_back_exact_and_is_counted(
        cluster, monkeypatch, handle):
    """Every id of every worker on shard 1: a bucket would need N slots
    and holds 48, so the op runs the gathered body in the same program,
    loses no slot, and ``engine.sparse.route.overflow`` counts it; the Zipf
    batches before and after fit and leave the count alone."""
    kv, eng = cluster
    idx, init, grads = _traffic(128)
    rng = np.random.default_rng(48)
    skew = (rng.integers(0, ROWS // W, size=(W, N)) * W + 1).astype(np.int32)
    assert (skew % W == 1).all() and skew.max() < ROWS
    skew[:, 1] = skew[:, 0]                 # duplicates in the fallback too
    eng.register_sparse("emb", ROWS, 128, init=init)
    over0 = _gauges(kv)["engine.sparse.route.overflow"]
    h = HANDLES[handle]
    kv.wait(kv.push_sparse("emb", idx[0], grads[0], h))
    assert _gauges(kv)["engine.sparse.route.overflow"] == over0
    kv.wait(kv.push_sparse("emb", skew, grads[1], h))
    assert _gauges(kv)["engine.sparse.route.overflow"] == over0 + 1
    out = np.zeros((W, N, 128), np.float32)
    kv.wait(kv.pull_sparse("emb", skew, out=out))
    assert _gauges(kv)["engine.sparse.route.overflow"] == over0 + 2
    kv.wait(kv.push_sparse("emb", idx[2], grads[2], h))
    kv.wait(kv.pull_sparse("emb", idx[2], out=np.zeros_like(out)))
    assert eng.route_overflows() == over0 + 2
    # Exact: the gathered engine fed the same four ops holds the same bits,
    # and under the sum the float64 table agrees.
    twin = _gathered_engine(monkeypatch, eng.mesh)
    twin.register_sparse("emb", ROWS, 128, init=init)
    twin.push("emb", idx[0], grads[0], h)
    twin.push("emb", skew, grads[1], h)
    assert (np.asarray(twin.pull("emb", skew)) == out).all() and out.any()
    twin.push("emb", idx[2], grads[2], h)
    got = np.asarray(eng.store_array("emb"))
    assert (got == np.asarray(twin.store_array("emb"))).all()
    if h is None:
        want = _reference(init, [idx[0], skew, idx[2]],
                          [grads[0], grads[1], grads[2]])
        table = got.reshape(W, -1, 128).transpose(1, 0, 2).reshape(-1, 128)
        assert _row_error(table[:ROWS], want) < TOL
    else:
        assert (np.asarray(eng.acc_array("emb"))
                == np.asarray(twin.acc_array("emb"))).all()


def _bucket_of(fill):
    """A batch whose worker 0 sends ``fill`` slots to shard 0 and the rest
    in equal parts to the others; the other workers spread theirs evenly."""
    rng = np.random.default_rng(fill)
    batch = np.empty((W, N), np.int32)
    for w in range(W):
        mine = fill if w == 0 else N // W
        owners = np.concatenate([np.zeros(mine, np.int64),
                                 1 + np.arange(N - mine) % (W - 1)])
        batch[w] = rng.permutation(
            rng.integers(0, ROWS // W, size=N) * W + owners)
    assert batch.max() < ROWS
    return batch


@pytest.mark.parametrize("fill, overflows", [(C, 0), (C + 3, 1)],
                         ids=["exactly-C", "over-C"])
def test_the_boundary_a_bucket_of_exactly_c_fits_and_one_more_does_not(
        fill, overflows):
    """48 slots of worker 0 for shard 0 fill its bucket to the last place
    and are routed; 51 fall back.  Exact against the float64 table either
    way, push and pull.  The count passes from op to op in place."""
    eng = SparseEngine(_mesh(W))
    _, init, grads = _traffic(128)
    batch = _bucket_of(fill)
    assert (batch[0] % W == 0).sum() == fill
    assert max((batch[w] % W == s).sum()
               for w in range(W) for s in range(W)) == fill
    eng.register_sparse("emb", ROWS, 128, init=init)
    jax.block_until_ready(eng.push("emb", batch, grads[0]))
    assert eng.route_overflows() == overflows
    want = _reference(init, [batch], [grads[0]])
    taken = eng._overflow["emb"]
    pulled = np.asarray(eng.pull("emb", batch))
    assert eng.route_overflows() == 2 * overflows
    # An op donates the count it takes and the table keeps what it returns:
    # a launch allocates nothing for the verdict.
    assert taken.is_deleted() and not eng._overflow["emb"].is_deleted()
    for w in range(W):
        assert _row_error(pulled[w], want[batch[w]]) < TOL, w


def test_one_shard_lowers_no_exchange_and_no_conditional():
    """S = 1 keeps the gathered body alone: the program takes no count,
    the compiled text holds no all-to-all, no conditional and no collective
    between devices, and an op's slots are its lookups."""
    import jax.numpy as jnp

    eng = SparseEngine(_mesh(1))
    table = eng.register_sparse("emb", ROWS, 128)
    idx = jnp.zeros((1, N), jnp.int32)
    g = jnp.zeros((1, N, 128), jnp.float32)
    for op, args in (("push", (idx, g)), ("pull", (idx,))):
        compiled = eng._sparse_program(op, table, N).lower(
            eng._stores["emb"], *args).compile().as_text()
        for word in ("all-to-all", "conditional", "collective-permute"):
            assert f" {word}(" not in compiled, (op, word)
        # What the CPU's compiler keeps of the gathered body's collectives
        # is among one replica, itself (a TPU's drops them).
        for line in compiled.splitlines():
            if re.search(r" (all-gather|all-reduce|reduce-scatter)\(", line):
                assert "replica_groups={{0}}" in line, line
    assert eng._route_slots(N) == N and not sparse._routes(1, N)
    jax.block_until_ready(eng.push("emb", np.zeros((1, N), np.int32),
                                   np.ones((1, N, 128), np.float32)))
    assert not eng._overflow and eng.route_overflows() == 0
    assert float(np.asarray(eng.pull("emb", np.zeros((1, 1), np.int32)))
                 [0, 0, 0]) == N


@pytest.mark.parametrize("handle", list(HANDLES))
def test_two_shards_route_and_match_the_gathered_body(monkeypatch, handle):
    """S = 2: buckets of 0.75 x n, 1.5 slots a lookup where the gathered
    form works on 2."""
    S = 2
    rng = np.random.default_rng(2)
    idx = [_zipf(rng, (S, N)) for _ in range(STEPS)]
    init = rng.normal(size=(ROWS, 128)).astype(np.float32)
    grads = [rng.normal(size=(S, N, 128)).astype(np.float32)
             for _ in range(STEPS)]
    routed = SparseEngine(_mesh(S))
    got = _run(routed, 128, HANDLES[handle], False, idx, init, grads)
    assert routed._route_slots(N) == S * 96 and routed.route_overflows() == 0
    gathered = _gathered_engine(monkeypatch, _mesh(S))
    want = _run(gathered, 128, HANDLES[handle], False, idx, init, grads)
    for part_got, part_want in zip(got, want):
        for a, b in zip(part_got, part_want):
            assert (a.view(np.uint32) == b.view(np.uint32)).all()
    if handle == "sum":
        table = got[0][0].reshape(S, -1, 128).transpose(1, 0, 2)
        assert _row_error(table.reshape(-1, 128)[:ROWS],
                          _reference(init, idx, grads)) < TOL


@pytest.mark.parametrize("handle", list(HANDLES))
def test_bags_over_four_servers_pool_after_the_rows_are_back(cluster, handle):
    """``pool="sum"`` over four shards (ISSUE 54): a worker's bags are routed
    a SLOT each (a bucket's gradient rows are read through the bag as they
    are bucketed) and pooled once the rows are back with the worker.  Three
    pooled pushes and a pooled pull of bags of 5 ids are, bit for bit, the
    unpooled ops of the bags multiplied out; a batch whose every id has one
    owner does not fit its buckets, falls back in the same program, stays
    exact and is counted."""
    kv, eng = cluster
    h, B = 5, N // 4                 # 160 slots a worker: buckets of 60
    rng = np.random.default_rng(54)
    init = rng.normal(size=(ROWS, 128)).astype(np.float32)
    bags = [_zipf(rng, (W, B, h)) for _ in range(STEPS)]
    for batch in bags:
        batch[:, 0, 0] = HOT            # every worker sends the hottest row
        batch[:, 1, 1] = batch[:, 1, 0]     # an id twice in one bag
    # Every id on shard 1: a bucket would need 160 slots and holds 60.
    bags[1] = (rng.integers(0, ROWS // W, size=(W, B, h)) * W + 1
               ).astype(np.int32)
    grads = [rng.normal(size=(W, B, 128)).astype(np.float32)
             for _ in range(STEPS)]
    eng.register_sparse("bags", ROWS, 128, init=init)
    eng.register_sparse("rows", ROWS, 128, init=init)
    hd = HANDLES[handle]
    over0 = eng.route_overflows()
    for i, g in zip(bags, grads):
        kv.wait(kv.push_sparse("bags", i, g, hd, pool="sum"))
        kv.wait(kv.push_sparse("rows", i.reshape(W, B * h),
                               np.repeat(g, h, axis=1), hd))
    assert eng.route_overflows() == over0 + 2       # bags[1], each way
    assert (np.asarray(eng.store_raw("bags")).view(np.uint32)
            == np.asarray(eng.store_raw("rows")).view(np.uint32)).all()
    if hd is not None:
        assert (np.asarray(eng.acc_array("bags"))
                == np.asarray(eng.acc_array("rows"))).all()
    ts = kv.pull_sparse("bags", bags[0], pool="sum")
    kv.wait(ts)
    pooled = np.asarray(kv.get_pulled(ts))
    ts = kv.pull_sparse("rows", bags[0].reshape(W, B * h))
    kv.wait(ts)
    rows = np.asarray(kv.get_pulled(ts)).reshape(W, B, h, 128)
    assert pooled.shape == (W, B, 128)
    want = rows.astype(np.float64).sum(axis=2)
    assert _row_error(pooled, want) < TOL
    if hd is None:
        table = _reference(init, [i.reshape(W, -1) for i in bags],
                           [np.repeat(g, h, axis=1) for g in grads])
        assert _row_error(pooled, table[bags[0]].sum(axis=2)) < TOL
    # The slots a shard works on are the lookups', 1.5 a lookup routed.
    assert eng._route_slots((B, h)) == W * sparse._capacity(W, B * h) \
        == W * 60
